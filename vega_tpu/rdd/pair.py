"""(K, V) pair operations, available on every RDD whose items are 2-tuples.

Reference: src/rdd/pair_rdd.rs — the PairRdd trait is blanket-implemented for
all Rdd<Item=(K,V)> (pair_rdd.rs:175-176); the Python analogue is a mixin on
the base RDD with runtime pair semantics. Op parity: combine_by_key (:20),
group_by_key (:35), reduce_by_key (:54), map_values (:82), flat_map_values
(:93), join (:104), cogroup (:123), partition_by_key (:157); vega_tpu adds the
outer joins, fold_by_key, keys/values, lookup, count_by_key, collect_as_map,
sort_by_key and aggregate_by_key that Spark users expect.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

from vega_tpu.aggregator import Aggregator
from vega_tpu.partitioner import HashPartitioner, Partitioner, RangePartitioner


class PairOpsMixin:
    """Mixed into RDD (vega_tpu/rdd/base.py)."""

    # --- shuffle-backed combiners -------------------------------------------------

    def combine_by_key(
        self,
        create_combiner: Callable,
        merge_value: Callable,
        merge_combiners: Callable,
        partitioner_or_num: Any = None,
    ):
        """Reference: pair_rdd.rs:20-33.

        When the parent is already partitioned by an equal partitioner the
        shuffle is elided and the combine runs as a narrow per-partition merge
        — the same partitioner-equality elision CoGroupedRDD applies
        (reference: co_grouped_rdd.rs:102-127)."""
        from vega_tpu.rdd.shuffled import ShuffledRDD

        partitioner = _resolve_partitioner(self, partitioner_or_num)
        agg = Aggregator(create_combiner, merge_value, merge_combiners)
        if self.partitioner is not None and self.partitioner == partitioner:
            from vega_tpu.rdd.narrow import MapPartitionsRDD

            def combine_locally(_idx, it):
                combiners: dict = {}
                for k, value in it:
                    if k in combiners:
                        combiners[k] = merge_value(combiners[k], value)
                    else:
                        combiners[k] = create_combiner(value)
                return iter(combiners.items())

            return MapPartitionsRDD(self, combine_locally,
                                    preserves_partitioning=True)
        return ShuffledRDD(self, agg, partitioner)

    def reduce_by_key(self, func: Callable, partitioner_or_num: Any = None):
        """Reference: pair_rdd.rs:54-80. Recognized monoids (add/min/max/
        prod) are tagged so numeric partitions take the native C++
        bucket-combine instead of the per-element Python loop."""
        from vega_tpu.rdd.shuffled import ShuffledRDD

        partitioner = _resolve_partitioner(self, partitioner_or_num)
        if not (self.partitioner is not None and self.partitioner == partitioner):
            op_name = _infer_named_op(func)
            if op_name is not None:
                agg = Aggregator(lambda v: v, func, func, op_name=op_name)
                return ShuffledRDD(self, agg, partitioner)
        return self.combine_by_key(
            lambda v: v, func, func, partitioner
        )

    def fold_by_key(self, zero, func: Callable, partitioner_or_num: Any = None):
        import copy

        return self.combine_by_key(
            lambda v: func(copy.deepcopy(zero), v), func, func, partitioner_or_num
        )

    def aggregate_by_key(self, zero, seq_func: Callable, comb_func: Callable,
                         partitioner_or_num: Any = None):
        import copy

        return self.combine_by_key(
            lambda v: seq_func(copy.deepcopy(zero), v),
            seq_func,
            comb_func,
            partitioner_or_num,
        )

    def group_by_key(self, partitioner_or_num: Any = None):
        """Reference: pair_rdd.rs:35-52 (default Vec-collecting aggregator)."""
        from vega_tpu.rdd.shuffled import ShuffledRDD

        partitioner = _resolve_partitioner(self, partitioner_or_num)
        return ShuffledRDD(self, Aggregator.default(), partitioner)

    def partition_by_key(self, partitioner_or_num: Any = None):
        """Repartition by key without combining (reference: pair_rdd.rs:157-173)."""
        return self.group_by_key(partitioner_or_num).flat_map_values(lambda vs: vs)

    partition_by = partition_by_key

    def count_by_key(self) -> dict:
        return dict(self.map_values(lambda _: 1).reduce_by_key(lambda a, b: a + b).collect())

    # --- value-side narrow ops ----------------------------------------------------

    def map_values(self, f: Callable):
        """Reference: pair_rdd.rs:82-91; preserves the partitioner
        (MappedValuesRdd, pair_rdd.rs:212-228)."""
        from vega_tpu.rdd.narrow import MapPartitionsRDD

        def apply(_idx, it):
            for k, v in it:
                yield (k, f(v))

        return MapPartitionsRDD(self, apply, preserves_partitioning=True)

    def flat_map_values(self, f: Callable):
        """Reference: pair_rdd.rs:93-102 (FlatMappedValuesRdd :320-340)."""
        from vega_tpu.rdd.narrow import MapPartitionsRDD

        def apply(_idx, it):
            for k, v in it:
                for out in f(v):
                    yield (k, out)

        return MapPartitionsRDD(self, apply, preserves_partitioning=True)

    def keys(self):
        return self.map(lambda kv: kv[0])

    def values(self):
        return self.map(lambda kv: kv[1])

    def mask_keys(self, pred: Callable):
        return self.filter(lambda kv: pred(kv[0]))

    # --- joins & cogroup ----------------------------------------------------------

    def cogroup(self, *others, partitioner_or_num: Any = None):
        """Reference: pair_rdd.rs:123-155 / co_grouped_rdd.rs."""
        from vega_tpu.rdd.cogrouped import CoGroupedRDD

        partitioner = _resolve_partitioner(self, partitioner_or_num, others)
        return CoGroupedRDD([self, *others], partitioner)

    group_with = cogroup

    def join(self, other, partitioner_or_num: Any = None):
        """Inner join (reference: pair_rdd.rs:104-121)."""

        def emit(groups):
            left, right = groups
            return [(l, r) for l in left for r in right]

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    def left_outer_join(self, other, partitioner_or_num: Any = None):
        def emit(groups):
            left, right = groups
            if not right:
                return [(l, None) for l in left]
            return [(l, r) for l in left for r in right]

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    def right_outer_join(self, other, partitioner_or_num: Any = None):
        def emit(groups):
            left, right = groups
            if not left:
                return [(None, r) for r in right]
            return [(l, r) for l in left for r in right]

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    def full_outer_join(self, other, partitioner_or_num: Any = None):
        def emit(groups):
            left, right = groups
            if not left:
                return [(None, r) for r in right]
            if not right:
                return [(l, None) for l in left]
            return [(l, r) for l in left for r in right]

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    def subtract_by_key(self, other, partitioner_or_num: Any = None):
        def emit(groups):
            left, right = groups
            return list(left) if not right else []

        return self.cogroup(
            other, partitioner_or_num=partitioner_or_num
        ).flat_map_values(emit)

    # --- ordering -----------------------------------------------------------------

    def sort_by_key(self, ascending: bool = True,
                    num_partitions: Optional[int] = None,
                    sample_size_hint: int = 1000):
        """Total sort via sampled RangePartitioner + per-partition sort.

        The reference has no sort_by_key (only take_ordered,
        rdd.rs:1124-1153); BASELINE config 5 requires a distributed sort, so
        vega_tpu implements the standard sample -> range-partition -> local
        sort pipeline.
        """
        from vega_tpu.rdd.narrow import MapPartitionsRDD
        from vega_tpu.rdd.shuffled import ShuffledRDD

        n_out = num_partitions or self.num_partitions
        if n_out <= 1:
            bounds: List = []
        else:
            frac = min(1.0, (sample_size_hint * n_out) / max(1, self.count()))
            keys = self.keys().sample(False, frac, seed=17).collect()
            if not keys:
                bounds = []
            else:
                keys.sort()
                step = len(keys) / n_out
                bounds = [keys[min(len(keys) - 1, int(step * i))]
                          for i in range(1, n_out)]
                bounds = sorted(set(bounds))
        partitioner = RangePartitioner(bounds, ascending)
        shuffled = ShuffledRDD(self, Aggregator.default(), partitioner)

        def sort_rows(_idx, it):
            rows = []
            for k, vs in it:
                for v in vs:
                    rows.append((k, v))
            rows.sort(key=lambda kv: kv[0], reverse=not ascending)
            return iter(rows)

        return MapPartitionsRDD(shuffled, sort_rows,
                                preserves_partitioning=True)

    # --- driver-side helpers ------------------------------------------------------

    def collect_as_map(self) -> dict:
        return dict(self.collect())

    def lookup(self, key) -> list:
        part = self.partitioner
        if part is not None:
            target = part.get_partition(key)
            results = self.context.run_job(
                self,
                lambda _tc, it: [v for k, v in it if k == key],
                partitions=[target],
            )
            return results[0]
        return self.filter(lambda kv: kv[0] == key).values().collect()


def _canonical_monoid_codes():
    """co_code of the canonical monoid lambdas for this interpreter."""
    return {
        (lambda a, b: a + b).__code__.co_code: "add",
        (lambda a, b: a * b).__code__.co_code: "prod",
    }


_MONOID_CODES = _canonical_monoid_codes()


def _infer_named_op(func: Callable):
    """Recognize the standard monoids SOUNDLY — only exact identities:
    operator.add/mul, builtin min/max, and lambdas whose bytecode equals the
    canonical `lambda a, b: a + b` / `a * b` (no free variables, no consts,
    no attribute lookups). Probing on sample values was rejected in review:
    any commutative function agreeing with a monoid at the probe points
    (e.g. lambda x, y: min(x + y, 100)) would be silently misclassified."""
    import operator

    if func is operator.add:
        return "add"
    if func is operator.mul:
        return "prod"
    if func is min:
        return "min"
    if func is max:
        return "max"
    code = getattr(func, "__code__", None)
    if (
        code is not None
        and code.co_argcount == 2
        and not code.co_freevars
        and not code.co_names
        and code.co_consts in ((), (None,))
        and getattr(func, "__closure__", None) is None
    ):
        return _MONOID_CODES.get(code.co_code)
    return None


def _resolve_partitioner(rdd, partitioner_or_num, others=()) -> Partitioner:
    """num | Partitioner | None -> Partitioner, defaulting to the max parent
    partition count (Spark convention; reference always requires explicit
    counts — we default sensibly)."""
    if isinstance(partitioner_or_num, Partitioner):
        return partitioner_or_num
    if partitioner_or_num is None:
        for r in (rdd, *others):
            if r.partitioner is not None:
                return r.partitioner
        n = max(r.num_partitions for r in (rdd, *others))
        return HashPartitioner(n)
    return HashPartitioner(int(partitioner_or_num))
