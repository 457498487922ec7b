"""File ingest (reference: src/io/local_file_reader.rs + src/io/mod.rs).

The reference's LocalFsReader walks a directory, assigns files to partitions
with size balancing (local_file_reader.rs:221-295), and pins each split to the
executor host that owns the files (:320-322,339-356) — data-parallel ingest
without a DFS. vega_tpu keeps the same model: FileSplitAssigner does the
size-balanced file->partition packing; readers are source RDDs pinned to their
host in distributed mode; parquet reads go through pyarrow straight into
columnar blocks the device tier can consume zero-copy.
"""

from __future__ import annotations

import glob as globlib
import os
from typing import Callable, Iterator, List, Optional

from vega_tpu.rdd.base import RDD
from vega_tpu.split import Split


def _discover(path: str) -> List[str]:
    """Directory walk / glob expansion (reference: local_file_reader.rs:149-217)."""
    if os.path.isdir(path):
        files = []
        for root, _dirs, names in os.walk(path):
            for name in sorted(names):
                if not name.startswith("."):
                    files.append(os.path.join(root, name))
        return sorted(files)
    matches = sorted(globlib.glob(path))
    if not matches and os.path.exists(path):
        matches = [path]
    return matches


def assign_files_to_partitions(files: List[str], num_partitions: int) -> List[List[str]]:
    """Size-balanced greedy packing: biggest file to least-loaded partition
    (reference: local_file_reader.rs:221-295)."""
    import heapq

    num_partitions = max(1, min(num_partitions, max(len(files), 1)))
    sized = sorted(
        ((os.path.getsize(f), f) for f in files), reverse=True
    )
    heap = [(0, i, []) for i in range(num_partitions)]
    heapq.heapify(heap)
    for size, f in sized:
        load, i, bucket = heapq.heappop(heap)
        bucket.append(f)
        heapq.heappush(heap, (load + size, i, bucket))
    buckets = [[] for _ in range(num_partitions)]
    for _load, i, bucket in heap:
        buckets[i] = bucket
    return [b for b in buckets if b] or [[]]


class _FileListRDD(RDD):
    """Source RDD over pre-assigned file groups; one partition per group."""

    def __init__(self, ctx, groups: List[List[str]],
                 read_group: Callable[[List[str]], Iterator],
                 host: Optional[str] = None):
        super().__init__(ctx)
        self._groups = groups
        self._read_group = read_group
        self._host = host
        if host is not None:
            self._pinned = True  # reference: local_file_reader.rs:320-322

    @property
    def num_partitions(self) -> int:
        return len(self._groups)

    def splits(self) -> List[Split]:
        return [Split(i, payload=g) for i, g in enumerate(self._groups)]

    def preferred_locations(self, split: Split) -> List[str]:
        return [self._host] if self._host else []

    def compute(self, split: Split, task_context=None) -> Iterator:
        return self._read_group(split.payload or self._groups[split.index])


class LocalFsReaderConfig:
    """Reference: src/io/local_file_reader.rs:20-78 (ReaderConfiguration).

    Yields raw file bytes, one item per file."""

    def __init__(self, path: str, num_partitions: int = 4,
                 host: Optional[str] = None):
        self.path = path
        self.num_partitions = num_partitions
        self.host = host

    def make_reader(self, ctx) -> RDD:
        groups = assign_files_to_partitions(
            _discover(self.path), self.num_partitions
        )

        def read_group(files: List[str]) -> Iterator[bytes]:
            for f in files:
                with open(f, "rb") as fh:
                    yield fh.read()

        return _FileListRDD(ctx, groups, read_group, self.host)


class WholeFileReaderConfig(LocalFsReaderConfig):
    """(path, bytes) per file."""

    def make_reader(self, ctx) -> RDD:
        groups = assign_files_to_partitions(
            _discover(self.path), self.num_partitions
        )

        def read_group(files: List[str]):
            for f in files:
                with open(f, "rb") as fh:
                    yield (f, fh.read())

        return _FileListRDD(ctx, groups, read_group, self.host)


class TextFileReaderConfig(LocalFsReaderConfig):
    """One item per line, like Spark's textFile."""

    def make_reader(self, ctx) -> RDD:
        groups = assign_files_to_partitions(
            _discover(self.path), self.num_partitions
        )

        def read_group(files: List[str]) -> Iterator[str]:
            for f in files:
                with open(f, "r", errors="replace") as fh:
                    for line in fh:
                        yield line.rstrip("\n")

        return _FileListRDD(ctx, groups, read_group, self.host)


# Predicate-pushdown conjunct operators (ParquetColumnReader.predicate):
# each conjunct is a (column, op, literal) triple. Row groups whose
# min/max statistics cannot satisfy a conjunct are skipped whole; rows
# surviving the row-group pass are mask-filtered per batch — either way
# the pruned rows never leave the reader.
_PRED_OPS = {
    "==": lambda c, v: c == v,
    "!=": lambda c, v: c != v,
    "<": lambda c, v: c < v,
    "<=": lambda c, v: c <= v,
    ">": lambda c, v: c > v,
    ">=": lambda c, v: c >= v,
}


def discover_parquet_files(path: str) -> List[str]:
    """Parquet file discovery with a crisp contract: expanding a directory
    or glob keeps only .parquet/.pq files and REFUSES loudly when none
    match (feeding an arbitrary matched file to pyarrow produces an
    undecipherable downstream stack trace); a single explicitly-named
    existing file is taken as-is (explicit path == user intent, whatever
    the extension)."""
    from vega_tpu.errors import VegaError

    files = _discover(path)
    if not files:
        raise VegaError(
            f"parquet read: path {path!r} matches no files"
        )
    if len(files) == 1 and files[0] == path and os.path.isfile(path):
        return files
    matched = [f for f in files if f.endswith((".parquet", ".pq"))]
    if not matched:
        raise VegaError(
            f"parquet read: no .parquet/.pq files under {path!r} — the "
            f"{len(files)} file(s) found there (e.g. "
            f"{os.path.basename(files[0])!r}) are not parquet; pass the "
            "file explicitly if the extension is just unconventional"
        )
    return matched


def _row_group_may_match(meta_rg, col_index: dict, predicate) -> bool:
    """False only when the row group's column statistics PROVE no row can
    satisfy the conjunct — missing/partial statistics keep the group."""
    for name, op, lit in predicate:
        idx = col_index.get(name)
        if idx is None:
            continue
        col = meta_rg.column(idx)
        stats = col.statistics
        if stats is None or not stats.has_min_max:
            continue
        lo, hi = stats.min, stats.max
        try:
            if op == "==" and (lit < lo or lit > hi):
                return False
            if op == "<" and lo >= lit:
                return False
            if op == "<=" and lo > lit:
                return False
            if op == ">" and hi <= lit:
                return False
            if op == ">=" and hi < lit:
                return False
        except TypeError:
            continue  # incomparable stats (e.g. bytes vs int): keep
    return True


_FILES_READ = [0]


def parquet_files_read() -> int:
    """Parquet files whose rows iter_parquet_batches has read, in this
    process: a cached frame's later actions leave it where it was."""
    return _FILES_READ[0]


def iter_parquet_batches(paths: List[str], columns: Optional[List[str]],
                         predicate=None, batch_rows: int = 1 << 20,
                         arrow_columns=None):
    """Yield {name: numpy column} dicts with column pruning AND predicate
    pushdown applied inside the reader. Columns the query never names and
    rows no conjunct can accept never leave the file layer.

    Columns named in `arrow_columns` (string columns) skip the numpy pivot:
    each is read as an arrow dictionary array (the file's dictionary pages
    straight through, no per-row Python objects) and yielded as that arrow
    array, for tpu/dict_encoding.encode_arrow to build one sorted
    dictionary from. Predicate columns are excluded — the conjunct mask
    evaluates on numpy values."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    predicate = list(predicate or ())
    arrow_columns = set(arrow_columns or ()) - {nm for nm, _o, _v
                                               in predicate}
    # Predicate columns must be read to evaluate the mask even when the
    # query output prunes them; they are dropped again after filtering.
    read_cols = columns
    if columns is not None and predicate:
        extra = [nm for nm, _op, _v in predicate if nm not in columns]
        read_cols = list(columns) + sorted(set(extra))
    for path in paths:
        _FILES_READ[0] += 1
        pf = pq.ParquetFile(path, read_dictionary=sorted(arrow_columns))
        names = pf.schema_arrow.names
        col_index = {nm: i for i, nm in enumerate(names)}
        if predicate:
            groups = [g for g in range(pf.metadata.num_row_groups)
                      if _row_group_may_match(pf.metadata.row_group(g),
                                              col_index, predicate)]
            if not groups:
                continue
        else:
            groups = range(pf.metadata.num_row_groups)
        # A row group a read: its batches share the group's dictionary
        # pages (iter_batches hands every batch a copy of a column's whole
        # dictionary, which a high-cardinality string column multiplies by
        # the batches of a group).
        for batch in (b for g in groups
                      for b in pf.read_row_group(g, columns=read_cols)
                      .to_batches(max_chunksize=batch_rows)):
            block = {}
            for i, name in enumerate(batch.schema.names):
                col = batch.column(i)
                block[name] = (col if name in arrow_columns
                               else col.to_numpy(zero_copy_only=False))
            if predicate:
                mask = None
                for nm, op, lit in predicate:
                    m = _PRED_OPS[op](block[nm], lit)
                    mask = m if mask is None else (mask & m)
                if mask is not None and not np.all(mask):
                    keep = pa.array(np.asarray(mask, dtype=bool))
                    block = {
                        nm: (c.filter(keep) if nm in arrow_columns
                             else c[mask])
                        for nm, c in block.items()
                    }
            if columns is not None:
                block = {nm: block[nm] for nm in columns}
            yield block


# Parquet METADATA cache, keyed on (abspath, mtime_ns, size): one frame
# compile consults schema, row counts and column statistics several times
# (entry-point schema, planner schema, size estimate, int32-fit proofs —
# and again on every action, since frames recompile per action), and each
# consult used to re-open the file's footer. One footer read per file
# version serves them all. Bounded: pruned crudely once it grows past
# _META_CACHE_MAX (fixture churn in tests).
_META_CACHE: dict = {}
_META_CACHE_MAX = 1024


def _file_meta(path: str) -> dict:
    import os as _os

    import numpy as np
    import pyarrow.parquet as pq

    st = _os.stat(path)
    key = (_os.path.abspath(path), st.st_mtime_ns, st.st_size)
    meta = _META_CACHE.get(key)
    if meta is not None:
        return meta
    pf = pq.ParquetFile(path)
    m = pf.metadata
    idx = {m.schema.column(i).name: i for i in range(m.num_columns)}
    minmax = {}
    for name, i in idx.items():
        lo = hi = None
        complete = True
        for g in range(m.num_row_groups):
            stats = m.row_group(g).column(i).statistics
            if stats is None or not stats.has_min_max:
                complete = False
                break
            try:
                lo = stats.min if lo is None else min(lo, stats.min)
                hi = stats.max if hi is None else max(hi, stats.max)
            except TypeError:  # incomparable stats values
                complete = False
                break
        minmax[name] = (lo, hi) if complete and lo is not None else None
    import pyarrow as pa

    nulls = {}
    for name, i in idx.items():
        total = 0
        for g in range(m.num_row_groups):
            stats = m.row_group(g).column(i).statistics
            if stats is None or stats.null_count is None:
                total = None
                break
            total += stats.null_count
        nulls[name] = total
    # a column written from an arrow dictionary array keeps that type in
    # the file's arrow schema: its values' type is the column's
    types = {f.name: (f.type.value_type if pa.types.is_dictionary(f.type)
                      else f.type) for f in pf.schema_arrow}
    dates = {nm for nm, t in types.items() if pa.types.is_date32(t)}
    meta = {
        # a date32 column is whole days: datetime64[D], not arrow's
        # pandas default of datetime64[ms]
        "schema": {nm: (np.dtype("datetime64[D]") if nm in dates
                        else t.to_pandas_dtype())
                   for nm, t in types.items()},
        "dates": dates,
        "strings": {nm for nm, t in types.items()
                    if pa.types.is_string(t) or pa.types.is_large_string(t)},
        "num_rows": m.num_rows,
        "minmax": minmax,
        "nulls": nulls,
    }
    if len(_META_CACHE) >= _META_CACHE_MAX:
        _META_CACHE.clear()
    _META_CACHE[key] = meta
    return meta


def parquet_schema(path: str) -> dict:
    """{column: numpy dtype} from file metadata only (no data read) — the
    frame planner's schema source."""
    return dict(_file_meta(discover_parquet_files(path)[0])["schema"])


def parquet_num_rows(path: str) -> int:
    """Total rows across the path's files, from metadata only (the frame
    planner's exchange-sizing estimate)."""
    return sum(_file_meta(f)["num_rows"]
               for f in discover_parquet_files(path))


def parquet_string_columns(path: str) -> set:
    """Column names with an arrow string/large_string type, from metadata
    only — the frame planner's dictionary-encoding eligibility source
    (a pandas-dtype `object` alone cannot distinguish string columns
    from arbitrary object columns)."""
    out: set = set()
    for f in discover_parquet_files(path):
        out |= _file_meta(f)["strings"]
    return out


def parquet_date_columns(path: str) -> set:
    """Column names with an arrow date32 type (whole days), from metadata
    only: the device holds them as int32 days since 1970-01-01."""
    out: set = set()
    for f in discover_parquet_files(path):
        out |= _file_meta(f)["dates"]
    return out


def parquet_column_nulls(path: str, column: str):
    """Total null count across the path's files from statistics, or None
    when any row group lacks them. Metadata only — the dictionary-encoded
    device path requires a proven null-free string column (codes have no
    null slot); unknown counts keep the column on the host tier."""
    total = 0
    for f in discover_parquet_files(path):
        n = _file_meta(f)["nulls"].get(column)
        if n is None:
            return None
        total += n
    return total


def parquet_column_minmax(path: str, column: str):
    """(min, max) over every row group's statistics, or None when any
    group lacks them. Metadata only — lets the frame planner prove an
    int64 column fits int32 without touching data."""
    lo = hi = None
    for f in discover_parquet_files(path):
        mm = _file_meta(f)["minmax"].get(column)
        if mm is None:
            return None
        lo = mm[0] if lo is None else min(lo, mm[0])
        hi = mm[1] if hi is None else max(hi, mm[1])
    return None if lo is None else (lo, hi)


class ParquetColumnReader:
    """Columnar parquet ingest (reference: examples/parquet_column_read.rs).

    Yields one pyarrow RecordBatch-derived dict of numpy column arrays per
    batch — the exact block format the device tier consumes, so
    parquet -> TPU needs no row pivot. `columns` prunes at the file layer;
    `predicate` ([(column, op, literal), ...] conjuncts, op in
    ==/!=/</<=/>/>=) skips row groups via statistics and mask-filters the
    survivors — the frame planner's pushdown hooks."""

    def __init__(self, path: str, columns: Optional[List[str]] = None,
                 num_partitions: int = 4, batch_rows: int = 1 << 20,
                 host: Optional[str] = None, predicate=None):
        self.path = path
        self.columns = columns
        self.num_partitions = num_partitions
        self.batch_rows = batch_rows
        self.host = host
        self.predicate = list(predicate or ())

    def make_reader(self, ctx) -> RDD:
        files = discover_parquet_files(self.path)
        groups = assign_files_to_partitions(files, self.num_partitions)
        columns = self.columns
        batch_rows = self.batch_rows
        predicate = self.predicate

        def read_group(paths: List[str]):
            yield from iter_parquet_batches(paths, columns, predicate,
                                            batch_rows)

        return _FileListRDD(ctx, groups, read_group, self.host)

    def rows(self, ctx) -> RDD:
        """Row-oriented view: yields per-row tuples (host tier)."""
        block_rdd = self.make_reader(ctx)

        def to_rows(block: dict):
            import numpy as np

            cols = list(block.values())
            n = len(cols[0]) if cols else 0
            for i in range(n):
                yield tuple(c[i] for c in cols)

        return block_rdd.flat_map(to_rows)


# Historical name (pre-frame API); same class, kept for callers and docs.
ParquetReaderConfig = ParquetColumnReader
