"""The DataFrame API — the user-facing face of the frame subsystem.

A DataFrame is an immutable (logical plan, options) pair; every verb
returns a new frame, and nothing is read, computed, or placed on device
until an ACTION runs (collect/collect_columns/count/take/to_rdd). This
module is the one place in vega_tpu/frame/ allowed to materialize —
VG013 keeps every other module plan-pure.

    df = ctx.read_parquet("events/")                 # -> DataFrame
    out = (df.select("user", "ms")
             .filter(col("ms") > 10)
             .with_column("s", col("ms") / 1000)
             .group_by("user").agg(F.sum("s"), F.count())
             .sort("user")
             .collect())

Tier selection, fusion, pushdown and per-exchange policy live in
planner.py; `hint()` exposes the knobs (fuse/pushdown/tier/exchange/
shuffle_plan)."""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from vega_tpu.errors import VegaError
from vega_tpu.frame import logical as L
from vega_tpu.frame import planner as planner_lib
from vega_tpu.frame.expr import Agg, Col, Expr, _as_expr


class DataFrame:
    def __init__(self, ctx, plan: L.LogicalPlan,
                 options: Optional[dict] = None):
        self._ctx = ctx
        self._plan = plan
        self._options = {**planner_lib.DEFAULT_OPTIONS, **(options or {})}

    # ------------------------------------------------------------ builders
    @staticmethod
    def from_parquet(ctx, path: str, columns: Optional[List[str]] = None,
                     num_partitions: Optional[int] = None) -> "DataFrame":
        from vega_tpu.io.readers import parquet_schema

        all_cols = list(parquet_schema(path))
        plan: L.LogicalPlan = L.ParquetScan(path, all_cols,
                                            num_partitions=num_partitions)
        if columns is not None:
            missing = [c for c in columns if c not in all_cols]
            if missing:
                raise VegaError(
                    f"unknown column(s) {missing} — parquet file "
                    f"{path!r} has {all_cols}")
            plan = L.Project(plan, [(c, Col(c)) for c in columns])
        return DataFrame(ctx, plan)

    @staticmethod
    def from_columns(ctx, data: dict,
                     num_partitions: Optional[int] = None) -> "DataFrame":
        if not data:
            raise VegaError("create_frame needs at least one column")
        arrays = {nm: np.asarray(c) for nm, c in data.items()}
        lens = {nm: len(c) for nm, c in arrays.items()}
        if len(set(lens.values())) > 1:
            raise VegaError(f"columns have unequal lengths: {lens}")
        return DataFrame(ctx, L.ColumnsScan(arrays, num_partitions))

    # --------------------------------------------------------------- verbs
    def _derive(self, plan: L.LogicalPlan) -> "DataFrame":
        if isinstance(self._plan, L.Limit):
            raise VegaError(
                "limit() is terminal — apply transformations before it")
        return DataFrame(self._ctx, plan, self._options)

    @property
    def columns(self) -> List[str]:
        return self._plan.columns()

    def select(self, *cols, **named) -> "DataFrame":
        """Positional args: column names or Exprs (Col exprs keep their
        name; other exprs need the keyword form). Keywords name computed
        columns: select(total=col("a") + col("b"))."""
        outputs = []
        for c in cols:
            if isinstance(c, str):
                outputs.append((c, Col(c)))
            elif isinstance(c, Col):
                outputs.append((c.name, c))
            else:
                raise VegaError(
                    "select() positional arguments must be column names; "
                    "use select(name=expr) for computed columns")
        outputs.extend((nm, _as_expr(e)) for nm, e in named.items())
        known = set(self.columns)
        for _nm, e in outputs:
            refs: set = set()
            e.references(refs)
            missing = refs - known
            if missing:
                raise VegaError(
                    f"unknown column(s) {sorted(missing)} — frame has "
                    f"{self.columns}")
        return self._derive(L.Project(self._plan, outputs))

    def _check_refs(self, expr: Expr, what: str) -> Expr:
        refs: set = set()
        expr.references(refs)
        missing = refs - set(self.columns)
        if missing:
            raise VegaError(
                f"{what} references unknown column(s) {sorted(missing)} — "
                f"frame has {self.columns}")
        return expr

    def with_column(self, name: str, expr) -> "DataFrame":
        expr = self._check_refs(_as_expr(expr), f"with_column({name!r})")
        outputs = [(c, Col(c)) for c in self.columns if c != name]
        outputs.append((name, expr))
        return self._derive(L.Project(self._plan, outputs))

    def rename(self, mapping: dict) -> "DataFrame":
        missing = set(mapping) - set(self.columns)
        if missing:
            raise VegaError(
                f"rename() references unknown column(s) {sorted(missing)}"
                f" — frame has {self.columns}")
        outputs = [(mapping.get(c, c), Col(c)) for c in self.columns]
        return self._derive(L.Project(self._plan, outputs))

    def filter(self, predicate) -> "DataFrame":
        predicate = self._check_refs(_as_expr(predicate), "filter()")
        return self._derive(L.Filter(self._plan, predicate))

    where = filter

    def group_by(self, key: str) -> "GroupedFrame":
        if key not in self.columns:
            raise VegaError(
                f"unknown group key {key!r} — frame has {self.columns}")
        return GroupedFrame(self, key)

    groupBy = group_by

    def join(self, other: "DataFrame", on: str, how: str = "inner",
             fill_value=0) -> "DataFrame":
        if not isinstance(other, DataFrame):
            raise VegaError("join() joins DataFrames; use to_rdd() for "
                            "RDD-level joins")
        if isinstance(other._plan, L.Limit):
            # Same build-time crispness _derive gives the left side.
            raise VegaError(
                "limit() is terminal — apply transformations (and joins) "
                "before it")
        for side, frame in (("left", self), ("right", other)):
            if on not in frame.columns:
                raise VegaError(
                    f"join column {on!r} missing on the {side} side "
                    f"({frame.columns})")
        return self._derive(L.Join(self._plan, other._plan, on, how,
                                   fill_value))

    def sort(self, by: str, ascending: bool = True) -> "DataFrame":
        if by not in self.columns:
            raise VegaError(
                f"unknown sort column {by!r} — frame has {self.columns}")
        return self._derive(L.Sort(self._plan, by, ascending))

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self._ctx, L.Limit(self._plan, n), self._options)

    def cache(self) -> "DataFrame":
        """This frame, kept: the first action that reads the returned frame
        (or any frame built on it) materializes its lineage once, and every
        later compile lowers to that same lineage, so later actions read
        no file and recompute nothing above it. The device block is cached
        as a DenseRDD's is (RDD.cache(): held until the HBM LRU of
        Configuration.dense_hbm_budget evicts it, then rebuilt from the
        lineage); a frame the host tier serves caches its RDD. Like
        Spark's, lazy: nothing is read until an action."""
        if isinstance(self._plan, L.Limit):
            raise VegaError("cache() a frame before limit(), not after")
        return DataFrame(self._ctx, L.CachedScan(
            self._plan, planner_lib.FrameCache(self._plan)), self._options)

    _HINT_VALUES = {
        "tier": ("auto", "device", "host"),
        "exchange": ("auto", "all_to_all", "ring", "staged"),
        "shuffle_plan": ("pull", "push"),
    }

    def hint(self, **hints) -> "DataFrame":
        """Planner knobs: fuse=, pushdown=, tier=('auto'|'device'|'host'),
        exchange=('auto'|'all_to_all'|'ring'|'staged') — 'auto' routes
        through the collective-aware exchange planner —
        shuffle_plan=('pull'|'push')."""
        unknown = set(hints) - set(planner_lib.DEFAULT_OPTIONS)
        if unknown:
            raise VegaError(
                f"unknown hint(s) {sorted(unknown)}; have "
                f"{sorted(planner_lib.DEFAULT_OPTIONS)}")
        # Values are validated here too: a typo'd tier="devcie" would
        # otherwise silently demote the crisp-error mode to auto.
        for key, allowed in self._HINT_VALUES.items():
            if key in hints and hints[key] is not None \
                    and hints[key] not in allowed:
                raise VegaError(
                    f"hint {key}={hints[key]!r} — valid values: {allowed}")
        for key in ("fuse", "pushdown"):
            if key in hints and not isinstance(hints[key], bool):
                raise VegaError(f"hint {key}= takes a bool, got "
                                f"{hints[key]!r}")
        return DataFrame(self._ctx, self._plan,
                         {**self._options, **hints})

    # ------------------------------------------------------------- actions
    def _compiled(self) -> planner_lib.Compiled:
        return planner_lib.compile_plan(self._ctx, self._plan,
                                        self._options)

    def explain(self) -> str:
        return self._compiled().explain()

    def _shuffle_plan_override(self):
        import contextlib

        plan = self._options.get("shuffle_plan")
        if plan is None:
            return contextlib.nullcontext()
        from vega_tpu.env import DeploymentMode, Env

        conf = Env.get().conf
        if conf.deployment_mode is not DeploymentMode.LOCAL \
                and str(conf.shuffle_plan).lower() != str(plan).lower():
            # Distributed executors snapshot VEGA_TPU_SHUFFLE_PLAN at
            # SPAWN time (backend._worker_knobs): flipping the driver
            # conf mid-run would change only the driver's reduce-side
            # placement preferences while workers keep the spawn-time
            # plan — actively worse than doing nothing. Honest no-op.
            import logging

            logging.getLogger("vega_tpu").warning(
                "hint(shuffle_plan=%r) ignored: distributed workers were "
                "spawned with shuffle_plan=%r and the knob is read "
                "worker-side at spawn — set it on the Context instead",
                plan, conf.shuffle_plan)
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def override():
            saved = conf.shuffle_plan
            conf.shuffle_plan = plan
            try:
                yield
            finally:
                conf.shuffle_plan = saved

        return override()

    def collect(self) -> list:
        """Rows as tuples in frame column order (single-column frames
        still yield 1-tuples — the shape never depends on the plan)."""
        return self._collect(self._compiled())

    def _collect(self, compiled: planner_lib.Compiled) -> list:
        """collect() of an already compiled plan of this frame."""
        cols = self._collect_columns(compiled)
        names = self.columns
        arrays = [np.asarray(cols[nm]) for nm in names]
        n = len(arrays[0]) if arrays else 0
        return [tuple(_pyval(a[i]) for a in arrays) for i in range(n)]

    def collect_columns(self) -> dict:
        """Columnar collect: {name: numpy array} — no per-row Python
        objects on the device path. A date column comes back as
        datetime64[D] from either tier."""
        return self._collect_columns(self._compiled())

    def _collect_columns(self, compiled: planner_lib.Compiled) -> dict:
        with self._shuffle_plan_override():
            if compiled.kind == "device":
                blk_cols = compiled.rdd.collect_arrays()
                out = {fn: (np.asarray(blk_cols[bn]).astype(
                    np.int64).astype("datetime64[D]")
                    if fn in compiled.dates else np.asarray(blk_cols[bn]))
                    for fn, bn in compiled.out}
            elif compiled.layout == "blocks":
                blocks = compiled.rdd.collect()
                out = {}
                for nm in compiled.cols:
                    parts = [np.asarray(b[nm]) for b in blocks]
                    out[nm] = (np.concatenate(parts) if parts
                               else np.empty((0,)))
            else:  # host rows
                # A limit over the row layout pulls partitions
                # incrementally via take() (sorted layouts are globally
                # ordered, so the prefix IS the answer); device plans
                # cannot shrink — a stage is one SPMD program, so their
                # limit (and the blocks layout's) slices client-side.
                rows = (compiled.rdd.take(compiled.limit)
                        if compiled.limit is not None
                        else compiled.rdd.collect())
                out = {}
                for i, nm in enumerate(compiled.cols):
                    out[nm] = _as_days(np.asarray([r[i] for r in rows]))
        if compiled.limit is not None:
            out = {nm: c[:compiled.limit] for nm, c in out.items()}
        return out

    def count(self) -> int:
        compiled = self._compiled()
        with self._shuffle_plan_override():
            if compiled.kind == "device":
                n = compiled.rdd.count()
            elif compiled.layout == "blocks":
                # Ship per-block lengths, not the blocks themselves.
                from vega_tpu.frame import physical as P

                n = sum(compiled.rdd.map(P.host_block_len).collect())
            else:
                n = compiled.rdd.count()
        if compiled.limit is not None:
            n = min(n, compiled.limit)
        return n

    def take(self, n: int) -> list:
        return self.limit(n).collect()

    def to_rdd(self):
        """The compiled lineage as an RDD of frame-ordered row tuples —
        the escape hatch to the full RDD API. Device plans hand back the
        DenseRDD's host row view; host plans the row lineage itself. A
        limited frame materializes its (small, by intent) limited rows
        and re-parallelizes them, so the limit is never silently
        dropped."""
        compiled = self._compiled()
        if compiled.limit is not None:
            return self._ctx.parallelize(self.collect())
        if compiled.kind == "device":
            order = [bn for _fn, bn in compiled.out]
            schema_order = [nm for nm, _dt in compiled.rdd._schema()]
            rdd = compiled.rdd.to_rdd()
            days = tuple(j for j, c in enumerate(compiled.cols)
                         if c in compiled.dates)
            if len(schema_order) == 1:
                return rdd.map(_reorder_row([0], days))
            idx = [schema_order.index(bn) for bn in order]
            # Reorder to frame order and convert numpy scalars to Python
            # natives (a date's days to its datetime.date), so device and
            # host to_rdd() rows are interchangeable.
            return rdd.map(_reorder_row(idx, days))
        if compiled.layout == "blocks":
            from vega_tpu.frame import physical as P

            return compiled.rdd.flat_map(P.host_block_rows(compiled.cols))
        return compiled.rdd


def _as_days(col: np.ndarray) -> np.ndarray:
    """A host-tier row column of dates (datetime.date objects, as rows
    carry them) as datetime64[D], the type the device tier collects."""
    import datetime

    if col.dtype.kind == "O" and len(col) and all(
            isinstance(x, datetime.date)
            and not isinstance(x, datetime.datetime) for x in col):
        return col.astype("datetime64[D]")
    return col


def _pyval(x):
    """numpy scalar -> Python native; object-column values pass through."""
    return x.item() if hasattr(x, "item") else x


def _reorder_row(idx: List[int], days=()):
    def run(row):
        if not isinstance(row, tuple):
            row = (row,)
        return tuple(_as_date(row[i]) if j in days else _pyval(row[i])
                     for j, i in enumerate(idx))

    return run


def _as_date(v):
    """int32 days since 1970-01-01 -> datetime.date."""
    return np.datetime64(int(v), "D").item()


class GroupedFrame:
    """group_by(key) cursor; agg(...) closes it back into a DataFrame."""

    def __init__(self, frame: DataFrame, key: str):
        self._frame = frame
        self._key = key

    def agg(self, *aggs: Agg) -> DataFrame:
        for a in aggs:
            if not isinstance(a, Agg):
                raise VegaError(
                    "agg() takes aggregate descriptors (F.sum/F.min/"
                    "F.max/F.count/F.mean)")
        return self._frame._derive(
            L.GroupAgg(self._frame._plan, self._key, list(aggs)))

    def count(self) -> DataFrame:
        from vega_tpu.frame.expr import F

        return self.agg(F.count())
