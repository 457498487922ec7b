"""Frame physical building blocks.

Device side: LAZY columnar sources — plan compilation constructs the node,
the first `block()` access reads the file / coerces the arrays (planning
itself never touches data or device: VG013). Host side: the picklable
per-partition closures the host-tier compile wires into ordinary RDD
lineages (columnar block stages, group-agg pivots, tuple combiners).

Dtype contract at the device boundary (the same degrade dense_from_numpy
applies): int64/uint64 columns whose values fit int32 narrow to int32;
float64 narrows to float32; bool widens to int32; a date (numpy
datetime64[D], parquet date32) becomes int32 days since 1970-01-01;
anything else — object dtypes, out-of-range int64, datetimes finer than a
day — makes the PLANNER compile the host tier instead (silent fallback,
never an error)."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from vega_tpu.frame import expr as expr_lib
from vega_tpu.frame.expr import evaluate


class HostFallback(Exception):
    """Raised during device lowering: compile the same logical plan on the
    host tier instead (the two-tier contract, silently)."""


# ---------------------------------------------------------------------------
# dtype coercion at the device boundary
# ---------------------------------------------------------------------------


def coerce_dtype(np_dtype) -> str:
    """numpy dtype -> device dtype name, or raise HostFallback.

    Unicode/bytes dtypes return "dict32": the column lowers to an int32
    dictionary CODE column plus a Block.dicts sidecar (tpu/dict_encoding);
    datetime64[D] returns "date32": int32 days (an int64 range check
    first: NaT and dates past int32's days stay on the host);
    object dtypes stay host-only at the dtype level — whether an object
    column is all-strings needs a value scan, which only the
    sources (coerced_dtype / make_*_source) may pay."""
    dt = np.dtype(np_dtype)
    if dt == np.bool_:
        return "int32"
    if dt.kind in ("i", "u"):
        if dt.itemsize <= 4 and dt != np.uint32:
            return "int32"
        return "int64?"  # needs a value-range check (fits-int32 proof)
    if dt.kind == "f":
        return "float32"
    if dt == np.dtype("datetime64[D]"):
        return "date32"
    if dt.kind in ("U", "S"):
        from vega_tpu.tpu import dict_encoding

        if dict_encoding.dict_enabled():
            return "dict32"
        raise HostFallback(
            f"string column (dtype {dt}) with dense_dict_enabled off")
    raise HostFallback(f"dtype {dt} has no device column form")


def coerced_dtype(name: str, col: np.ndarray) -> Tuple[np.dtype, bool]:
    """(device dtype one host column will coerce to, is_dictionary) —
    CHECK only (dtype kind + the int64 range proof + the all-str object
    scan), no copy; the astype/encode itself runs at materialization (a
    date's to `days`). Raises HostFallback when the host tier must serve."""
    from vega_tpu.tpu import dict_encoding

    col = np.asarray(col)
    if col.dtype.kind == "O":
        # Object columns are host-only UNLESS every element is a str
        # (the pandas pivot shape) — a full scan, but the same class of
        # compile-time value check as the int64 range proof below.
        if dict_encoding.is_string_array(col):
            if dict_encoding.dict_enabled():
                return np.dtype(np.int32), True
            raise HostFallback(
                f"string column {name!r} with dense_dict_enabled off")
        raise HostFallback(
            f"column {name!r} (object dtype) has no device column form")
    kind = coerce_dtype(col.dtype)
    if kind == "dict32":
        return np.dtype(np.int32), True
    if kind in ("int64?", "date32"):
        info = np.iinfo(np.int32)
        raw = col.view(np.int64) if kind == "date32" else col
        if len(raw) and (raw.min() < info.min or raw.max() > info.max):
            raise HostFallback(
                f"column {name!r} holds values beyond int32 range")
        kind = "int32"
    return np.dtype(kind), False


def days(col: np.ndarray) -> np.ndarray:
    """A datetime64[D] column as the device holds it: int32 days since
    1970-01-01 (coerced_dtype proved the range)."""
    return np.asarray(col).view(np.int64).astype(np.int32)


def date_literal(value) -> np.int32:
    """A day literal (datetime.date, datetime64[D]) as int32 days since
    1970-01-01, the device's form of a date column."""
    return np.int32(np.datetime64(value, "D").astype(np.int64))


# ---------------------------------------------------------------------------
# lazy device sources
# ---------------------------------------------------------------------------
# Imported lazily inside the factories: this module is imported by the
# planner, and dense_rdd pulls in jax — keep that off the frame import
# path until a device plan is actually built.


def make_columns_source(ctx, data: Dict[str, np.ndarray],
                        names: List[Tuple[str, str]]):
    """Lazy dense source over in-memory columns. `names` maps
    (frame_name, block_name); dtypes are validated eagerly (pure numpy —
    the silent-fallback decision must happen at compile time), data is
    sharded onto the mesh only at first materialization."""
    from vega_tpu.tpu import mesh as mesh_lib
    from vega_tpu.tpu.dense_rdd import DenseRDD

    import jax.numpy as jnp

    # Compile time pays only the dtype/range CHECK (the tier decision
    # needs exactly that); the astype copies run at materialization, so
    # explain() and plan construction stay O(metadata) and the closure
    # pins no second copy of the data.
    dtypes = {}
    dict_bns = set()   # block names that are dictionary (string) columns
    dict_fns = set()   # same set, frame-name side (planner gates)
    date_fns = set()   # frame names of date columns (int32 days)
    for fn, bn in names:
        dtypes[bn], is_dict = coerced_dtype(fn, data[fn])
        if is_dict:
            dict_bns.add(bn)
            dict_fns.add(fn)
        elif np.asarray(data[fn]).dtype.kind == "M":
            date_fns.add(fn)
    name_pairs = list(names)
    enc_memo: Dict[str, tuple] = {}  # bn -> (codes, sorted values)

    def _encoded(fn: str, bn: str):
        # One encode total, shared between _dicts() (graph-build gates /
        # unification need the dictionaries) and _materialize.
        if bn not in enc_memo:
            from vega_tpu.tpu import dict_encoding

            enc_memo[bn] = dict_encoding.encode_array(np.asarray(data[fn]))
        return enc_memo[bn]

    class _ColumnsDenseSource(DenseRDD):
        _frame_dict_cols = frozenset(dict_fns)
        _frame_date_cols = frozenset(date_fns)

        def _schema(self):
            return tuple((bn, jnp.dtype(dtypes[bn]))
                         for _fn, bn in name_pairs)

        def _fp_extra(self):
            return tuple((bn, str(dtypes[bn]), bn in dict_bns,
                          len(data[fn]))
                         for fn, bn in name_pairs)

        def _dicts(self):
            return {bn: _encoded(fn, bn)[1]
                    for fn, bn in name_pairs if bn in dict_bns}

        def _materialize(self):
            from vega_tpu.tpu import block as block_lib

            cols = {}
            dicts = {}
            for fn, bn in name_pairs:
                if bn in dict_bns:
                    cols[bn], dicts[bn] = _encoded(fn, bn)
                elif fn in date_fns:
                    cols[bn] = days(data[fn])
                else:
                    cols[bn] = np.asarray(data[fn]).astype(dtypes[bn],
                                                           copy=False)
            return block_lib.from_numpy(cols, self.mesh,
                                        wide_values=False,
                                        dicts=dicts or None)

        def unpersist(self):
            return self  # source: host copy IS the data; nothing to free

    return _ColumnsDenseSource(ctx, mesh_lib.default_mesh())


def make_parquet_source(ctx, path: str, columns: List[str],
                        predicate, names: List[Tuple[str, str]],
                        dtypes: Dict[str, np.dtype]):
    """Lazy dense source over a parquet path with pruning + predicate
    pushdown applied INSIDE the reader. Compile time touches metadata
    only (schema, min/max statistics); the file is read at first
    materialization."""
    from vega_tpu.io.readers import (discover_parquet_files,
                                     iter_parquet_batches,
                                     parquet_column_minmax,
                                     parquet_column_nulls,
                                     parquet_date_columns,
                                     parquet_string_columns)
    from vega_tpu.tpu import mesh as mesh_lib
    from vega_tpu.tpu.dense_rdd import DenseRDD

    import jax.numpy as jnp

    string_cols = parquet_string_columns(path)
    date_cols = parquet_date_columns(path)
    for nm, _op, _lit in predicate:
        if nm in string_cols:
            # A pushed-down conjunct evaluates as a numpy mask inside the
            # reader; there is no device-side literal-encode yet, so a
            # string predicate keeps the whole scan on the host tier.
            raise HostFallback(
                f"pushed-down predicate on string column {nm!r} — "
                "host tier filters it")
    out_dtypes = {}
    dict_bns = set()
    dict_fns = set()
    date_fns = set()
    for fn, bn in names:
        if fn in string_cols:
            from vega_tpu.tpu import dict_encoding

            if not dict_encoding.dict_enabled():
                raise HostFallback(
                    f"parquet string column {fn!r} with "
                    "dense_dict_enabled off")
            # Dictionary codes have no null slot: the device path needs a
            # statistics PROOF the column is null-free (same move as the
            # int64 fits-int32 proof — metadata only, never data).
            nulls = parquet_column_nulls(path, fn)
            if nulls is None or nulls > 0:
                raise HostFallback(
                    f"parquet string column {fn!r} has nulls (or no "
                    "null-count statistics); codes have no null slot")
            out_dtypes[bn] = np.dtype(np.int32)
            dict_bns.add(bn)
            dict_fns.add(fn)
            continue
        if fn in date_cols:
            nulls = parquet_column_nulls(path, fn)
            if nulls is None or nulls > 0:
                raise HostFallback(
                    f"parquet date column {fn!r} has nulls (or no "
                    "null-count statistics); int32 days have no null slot")
            out_dtypes[bn] = np.dtype(np.int32)
            date_fns.add(fn)
            continue
        kind = coerce_dtype(dtypes[fn])
        if kind == "dict32":
            # parquet_string_columns covers arrow string types; a 'U'/'S'
            # pandas dtype without one would be a metadata mismatch.
            raise HostFallback(
                f"parquet column {fn!r}: string dtype without an arrow "
                "string type — host tier serves it")
        if kind == "date32":
            raise HostFallback(
                f"parquet column {fn!r}: a day dtype without an arrow "
                "date32 type — host tier serves it")
        if kind == "int64?":
            mm = parquet_column_minmax(path, fn)
            info = np.iinfo(np.int32)
            if mm is None or mm[0] < info.min or mm[1] > info.max:
                raise HostFallback(
                    f"parquet column {fn!r} is int64 with no proof it "
                    "fits int32 (missing stats or out of range)")
            kind = "int32"
        out_dtypes[bn] = np.dtype(kind)
    files = discover_parquet_files(path)
    name_pairs = list(names)
    enc_memo: Dict[str, object] = {}  # bn -> sorted dictionary

    def _encode(bn: str, chunks: list):
        """One string column's arrow chunks -> its codes, over the one
        sorted dictionary of this source (built once: dict_encoding)."""
        from vega_tpu.tpu import dict_encoding

        codes, values = dict_encoding.encode_arrow(chunks)
        if bn in enc_memo and len(enc_memo[bn]) == len(values) \
                and enc_memo[bn].arrow.equals(values.arrow):
            values = enc_memo[bn]  # the dictionary _dicts() handed out
        enc_memo[bn] = values
        return codes

    def _read_encoded():
        """One pass over the files; string columns arrive as arrow
        dictionary arrays off the file's dictionary pages (no object-array
        pivot) and are coded onto ONE sorted dictionary per column; dates
        as int32 days."""
        parts: Dict[str, list] = {fn: [] for fn, _bn in name_pairs}
        for batch in iter_parquet_batches(files, columns, predicate,
                                          arrow_columns=dict_fns):
            for fn, _bn in name_pairs:
                parts[fn].append(batch[fn])
        cols = {}
        for fn, bn in name_pairs:
            if bn in dict_bns:
                cols[bn] = _encode(bn, parts[fn])
                parts[fn] = None  # the arrow chunks go before the next
                continue
            stacked = (np.concatenate(parts[fn]) if parts[fn]
                       else np.empty((0,), dtypes[fn]))
            parts[fn] = None
            cols[bn] = (days(stacked) if fn in date_fns
                        else stacked.astype(out_dtypes[bn], copy=False))
        return cols, ({bn: enc_memo[bn] for bn in dict_bns} or None)

    class _ParquetDenseSource(DenseRDD):
        _frame_dict_cols = frozenset(dict_fns)
        _frame_date_cols = frozenset(date_fns)

        def _schema(self):
            return tuple((bn, jnp.dtype(out_dtypes[bn]))
                         for _fn, bn in name_pairs)

        def _fp_extra(self):
            return (path, tuple(columns), tuple(map(tuple, predicate)),
                    tuple(sorted((bn, str(dt))
                                 for bn, dt in out_dtypes.items())),
                    tuple(sorted(dict_bns)))

        def _dicts(self):
            if dict_bns and not enc_memo:
                # Graph-build consumers (keyed-op unification) need the
                # dictionaries before an action: one column-pruned read
                # of JUST the string columns, memoized so _materialize
                # hands out the identical dictionary objects.
                sub = [fn for fn, bn in name_pairs if bn in dict_bns]
                pieces: Dict[str, list] = {fn: [] for fn in sub}
                for batch in iter_parquet_batches(
                        files, sub, predicate, arrow_columns=set(sub)):
                    for fn in sub:
                        pieces[fn].append(batch[fn])
                for fn, bn in name_pairs:
                    if bn in dict_bns:
                        _encode(bn, pieces[fn])
            return {bn: enc_memo[bn] for bn in dict_bns}

        def _materialize(self):
            import pyarrow as pa

            from vega_tpu.tpu import block as block_lib

            cols, dicts = _read_encoded()
            # the read's arrow buffers are garbage now; arrow's allocator
            # keeps freed pages, as much again as the table, unless told
            pa.default_memory_pool().release_unused()
            return block_lib.from_numpy(cols, self.mesh, wide_values=False,
                                        dicts=dicts)

        def unpersist(self):
            return self  # re-read is the recompute; nothing cheaper to drop

    return _ParquetDenseSource(ctx, mesh_lib.default_mesh())


# ---------------------------------------------------------------------------
# host-tier per-partition closures (picklable; cloudpickle ships them)
# ---------------------------------------------------------------------------


def host_block_stage(colmap: List[Tuple[str, str]], steps,
                     emit: List[Tuple[str, object]]):
    """Columnar host stage over one {name: np column} block: the same
    project/filter step list the device stage fuses, evaluated with
    numpy. Returns a new {out_name: column} block."""

    def run(block: dict) -> dict:
        env = {fn: block[bn] for fn, bn in colmap}
        n = len(next(iter(env.values()))) if env else 0
        for kind, payload in steps:
            if kind == "project":
                new_env = {}
                for nm, e in payload:
                    new_env[nm] = _host_broadcast(evaluate(e, env, host=True),
                                                  n)
                env = new_env
            else:  # filter
                keep = _host_broadcast(
                    evaluate(payload, env, host=True), n)
                keep = np.asarray(keep, dtype=bool)
                env = {nm: c[keep] for nm, c in env.items()}
                n = len(next(iter(env.values()))) if env else 0
        return {bn: _host_broadcast(evaluate(e, env, host=True), n)
                for bn, e in emit}

    return run


def _host_broadcast(v, n: int):
    arr = np.asarray(v)
    if arr.ndim == 0:
        return np.full(n, arr[()])
    return arr


def host_block_to_pairs(key_name: str, specs: List[Tuple[str, object]],
                        scalar: bool = False):
    """Pivot a columnar block into (key, value) rows for the host
    group-agg: specs are (alias, Expr) in output order; `scalar=True`
    (single-aggregate plans) emits the bare value instead of a 1-tuple so
    the shuffle can ride the native monoid merge — which is what lets the
    push plan pre-merge it server-side. Keys become Python natives so
    hashing/equality match the device collect's tolist view."""

    def run(block: dict):
        env = dict(block)
        n = len(next(iter(env.values()))) if env else 0
        keys = np.asarray(env[key_name])
        vals = [_host_broadcast(evaluate(e, env, host=True), n)
                for _alias, e in specs]
        if scalar:
            v0 = np.asarray(vals[0])
            for i in range(n):
                yield (_item(keys[i]), _item(v0[i]))
            return
        arrays = [np.asarray(v) for v in vals]
        for i in range(n):
            yield (_item(keys[i]), tuple(_item(a[i]) for a in arrays))

    return run


def _item(x):
    """Element -> Python native; object-column elements (str, ...) pass
    through — the documented host fallback must serve them, not crash."""
    return x.item() if hasattr(x, "item") else x


_HOST_OPS = {
    "add": lambda a, b: a + b,
    "min": min,
    "max": max,
}


def host_tuple_combiner(ops: List[str]):
    """Elementwise tuple monoid combine for the host reduce — the exact
    host analogue of the device's named / traced-tuple segment reduce."""

    def combine(a, b):
        return tuple(_HOST_OPS[op](x, y) for op, x, y in zip(ops, a, b))

    return combine


def host_rows_stage(cols: List[str], steps,
                    emit: List[Tuple[str, object]]):
    """Rowwise host stage over (c0, c1, ...) tuples (the post-exchange
    layout): evaluates the same expression trees per row."""

    def run(row: tuple):
        env = dict(zip(cols, row))
        for kind, payload in steps:
            if kind == "project":
                env = {nm: evaluate(e, env, host=True)
                       for nm, e in payload}
            else:
                raise AssertionError("row-layout filters lower via filter()")
        return tuple(_native(evaluate(e, env, host=True))
                     for _nm, e in emit)

    return run


def host_rows_filter(cols: List[str], predicate):
    def run(row: tuple) -> bool:
        env = dict(zip(cols, row))
        return bool(evaluate(predicate, env, host=True))

    return run


def _native(v):
    arr = np.asarray(v)
    if arr.ndim == 0:
        return arr[()].item() if hasattr(arr[()], "item") else arr[()]
    return v


def host_rows_to_pairs(cols: List[str], key_name: str,
                       specs: List[Tuple[str, object]],
                       scalar: bool = False):
    """Rowwise pivot to (key, value[-tuple]) for a group-agg over the
    post-exchange row layout (scalar: see host_block_to_pairs)."""

    def run(row: tuple):
        env = dict(zip(cols, row))
        k = _native(env[key_name])
        if scalar:
            return (k, _native(evaluate(specs[0][1], env, host=True)))
        return (k, tuple(_native(evaluate(e, env, host=True))
                         for _alias, e in specs))

    return run


def host_pair_to_row():
    """(k, v) -> (k, v) row tuple (scalar single-aggregate finalize)."""

    def run(pair):
        return (pair[0], pair[1])

    return run


def host_finalize_slots(slots: List[tuple]):
    """(key, value-tuple) -> row. slots: ('v', i) picks vals[i];
    ('mean', i, j) emits vals[i] / vals[j]."""

    def run(pair):
        k, vals = pair
        out = [k]
        for slot in slots:
            if slot[0] == "v":
                out.append(vals[slot[1]])
            else:
                out.append(vals[slot[1]] / vals[slot[2]])
        return tuple(out)

    return run


def host_block_rows(cols: List[str]):
    """Columnar block -> row tuples (cols order), Python natives."""

    def run(block: dict):
        arrays = [np.asarray(block[c]) for c in cols]
        n = len(arrays[0]) if arrays else 0
        for i in range(n):
            yield tuple(_item(a[i]) for a in arrays)

    return run


def host_block_len(block: dict) -> int:
    """Row count of one columnar block — count() ships this instead of
    the blocks themselves."""
    return len(next(iter(block.values()))) if block else 0


def host_row_to_pair(idx: int):
    """Row tuple -> (key, rest-tuple) keyed on column index `idx`."""

    def run(row: tuple):
        return (row[idx], row[:idx] + row[idx + 1:])

    return run


def host_join_rows():
    """(k, (lrest, rrest)) -> (k, *lrest, *rrest)."""

    def run(pair):
        k, (lrest, rrest) = pair
        return (k,) + tuple(lrest) + tuple(rrest)

    return run


def host_left_join_emit(r_arity: int, fill_value):
    """Cogroup groups -> left-outer rows with an explicit fill (matching
    the device kernel's fill_value semantics, so results do not depend on
    which tier ran)."""

    def run(pair):
        k, (lvs, rvs) = pair
        if not rvs:
            fill = (fill_value,) * r_arity
            return [(k,) + tuple(lv) + fill for lv in lvs]
        return [(k,) + tuple(lv) + tuple(rv) for lv in lvs for rv in rvs]

    return run
