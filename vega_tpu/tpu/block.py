"""Columnar partition blocks: the device tier's unit of data.

A Block is the TPU-native replacement for the reference's per-partition item
iterators (rdd/rdd.rs:179-183): named columns stored as one global array each,
sharded row-wise over the mesh, plus a per-shard valid-row count. Static
per-shard capacity keeps every shape XLA-compilable; raggedness lives in
`counts`, never in shapes (SURVEY.md §7 hard part 1).

Layout: each column is [n_shards * capacity, ...] sharded on axis 0; rows
[s*capacity, s*capacity + counts[s]) are shard s's valid rows.
"""

from __future__ import annotations

import dataclasses
import math
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from vega_tpu.lint.sync_witness import named_lock
from vega_tpu.tpu import mesh as mesh_lib
from vega_tpu.tpu import spans

_host_cache_lock = named_lock("tpu.block._host_cache_lock")  # serializes Block.host_cols fills

KEY = "k"  # canonical key column
VALUE = "v"  # canonical value column
# Wide (two-column int64) encoding. TPUs have no native int64 and jax x64
# is off, so an int64 column beyond int32 range splits into
# <name> = high 32 bits (signed: preserves order) and <name>.lo = low 32
# bits stored sign-bit-flipped (signed compare of the stored word ==
# unsigned compare of the true low word), making lexicographic
# (<name>, <name>.lo) order equal int64 order. Host-facing reads
# reassemble the int64 transparently. Keys AND value columns use the same
# encoding; the ".lo" suffix is reserved in user column names.
LO_SUFFIX = ".lo"
KEY_LO = KEY + LO_SUFFIX
_LO_BIAS = np.uint32(0x80000000)
_LO_BIAS_I32 = np.int32(-2**31)  # the same bit, for int32 words
# Where an int64's high and low 32 bits sit among its two int32 words.
_LO_WORD, _HI_WORD = (0, 1) if sys.byteorder == "little" else (1, 0)
# decode_i64 joins this many rows at a time, so that a chunk of `out`
# is still in cache when its second word is written (64Mi rows: 0.22 s
# against 0.33 s unchunked on the host that sized it).
_DECODE_CHUNK_ROWS = 1 << 18
# to_numpy fills a result of this many bytes or more on the decode pool:
# glibc maps every array over 32 MiB afresh (its largest mmap threshold),
# so from here on the fill writes pages touched for the first time, which
# a TPU v5e host (gVisor) faults in at about 4 us a page, 0.9-1.0 GB/s on
# one thread; smaller results come from the heap's touched pages.
_THREADED_FILL_BYTES = 32 << 20
# The decode pool's threads. On a TPU v5e host (13 cores) the fill of a
# fresh 64Mi-row (int64 key, float32) result, 805 MB, took 1.22 s on 1
# thread, 0.59 s on 2, 0.39 s on 4, 0.28 s on 8 and 0.25 s on 12: the page
# faults run side by side, and little is left past 8.
_FILL_THREADS = min(8, os.cpu_count() or 1)
# Each task of a threaded fill writes about this many bytes of one column,
# so a single shard's column splits over every thread.
_FILL_TASK_BYTES = 16 << 20


def lo_of(name: str) -> str:
    return name + LO_SUFFIX


def is_lo(name: str) -> bool:
    return name.endswith(LO_SUFFIX)


def wide_value_pairs(names) -> dict:
    """{base: base+'.lo'} for every NON-KEY wide column pair present."""
    s = set(names)
    return {nm: lo_of(nm) for nm in s
            if not is_lo(nm) and nm != KEY and lo_of(nm) in s}


def encode_i64(src: np.ndarray):
    """int64 column -> (hi int32, biased-lo int32), order-preserving."""
    a = src.astype(np.int64, copy=False)
    hi = (a >> 32).astype(np.int32)
    lo = ((a & np.int64(0xFFFFFFFF)).astype(np.uint32)
          ^ _LO_BIAS).view(np.int32)
    return hi, lo


def decode_i64(hi: np.ndarray, lo: np.ndarray,
               out: Optional[np.ndarray] = None) -> np.ndarray:
    """Inverse of encode_i64, in one pass: an int64's two halves ARE the
    two stored words (`lo` with its sign bit flipped back), so each word
    is written straight into its half of `out` — no 64-bit temporaries.
    `out` (C-contiguous int64 of hi's shape, e.g. a slice of a larger
    result) is filled in place and returned; a fresh array otherwise."""
    hi = np.asarray(hi)
    lo = np.asarray(lo).view(np.int32)
    if out is None:
        out = np.empty(hi.shape, np.int64)
    words = out.view(np.int32).reshape(out.shape + (2,))
    for at in range(0, len(hi), _DECODE_CHUNK_ROWS):
        rows = slice(at, at + _DECODE_CHUNK_ROWS)
        words[rows, ..., _HI_WORD] = hi[rows]
        np.bitwise_xor(lo[rows], _LO_BIAS_I32, out=words[rows, ..., _LO_WORD])
    return out


def _fill(run) -> None:
    """Write one (dst, src, lo) run of valid rows into its place in a
    result column: a plain column by a copy, a wide pair by decode_i64."""
    dst, src, lo = run
    if lo is None:
        dst[...] = src
    else:
        decode_i64(src, lo, out=dst)


def _fill_runs(fills, counts, task_bytes: Optional[int] = None):
    """The (dst, src, lo) views _fill writes: for every shard and column, the
    shard's valid rows whole, or cut into runs of about `task_bytes`
    written. `fills` holds (result column, its shards, its .lo's or None)."""
    at = 0
    for s, c in enumerate(int(c) for c in counts):
        for dst, src, lo in fills:
            row = max(1, dst.itemsize * math.prod(dst.shape[1:]))
            step = c if task_bytes is None else max(1, task_bytes // row)
            for r in range(0, c, max(1, step)):
                e = min(r + step, c)
                yield (dst[at + r:at + e], src[s][r:e],
                       None if lo is None else lo[s][r:e])
        at += c


_fill_pool: Optional[ThreadPoolExecutor] = None
_fill_pool_lock = named_lock("tpu.block._fill_pool_lock")


def _decode_pool() -> ThreadPoolExecutor:
    """The decode's own worker threads, made at first use. Nothing else
    is handed this pool, and its tasks only copy and xor numpy arrays (both
    release the interpreter lock): no task waits on another."""
    global _fill_pool
    if _fill_pool is None:
        with _fill_pool_lock:
            if _fill_pool is None:
                _fill_pool = ThreadPoolExecutor(
                    _FILL_THREADS, thread_name_prefix="vega-decode")
    return _fill_pool


def _decode_key_cols(cols: dict) -> dict:
    """Reassemble every (name, name.lo) wide pair — key or value — into
    one int64 column for host-facing reads; other columns pass through
    (order preserved)."""
    if not any(is_lo(n) for n in cols):
        return cols
    out = {}
    for name, col in cols.items():
        if is_lo(name):
            continue
        lo = cols.get(lo_of(name))
        out[name] = col if lo is None else decode_i64(col, lo)
    return out


def _decode_dict_cols(cols: dict, dicts) -> dict:
    """Turn dictionary-encoded int32 code columns back into their string
    columns for host-facing reads (the collect-boundary decode of
    tpu/dict_encoding.py); non-dict columns pass through (order
    preserved). Runs AFTER _decode_key_cols — dict names never carry a
    '.lo' pair, so the two decodes touch disjoint columns."""
    if not dicts:
        return cols
    from vega_tpu.tpu.dict_encoding import decode_codes

    return {name: (decode_codes(col, dicts[name]) if name in dicts else col)
            for name, col in cols.items()}


def _shard_buffers(col, capacity: int):
    """The single-device buffers behind a fully addressable jax.Array
    column, placed by the rows each holds (not by list order): entry s
    is block shard s's `capacity` rows. None where the column is not a
    jax.Array laid out one block shard a device."""
    if not isinstance(col, jax.Array) \
            or col.sharding.shard_shape(col.shape)[0] != capacity:
        return None
    by_shard = {(sh.index[0].start or 0) // capacity: sh.data
                for sh in col.addressable_shards}
    return [by_shard[s] for s in range(len(by_shard))]


@dataclasses.dataclass
class Block:
    cols: Dict[str, jax.Array]  # each [n_shards * capacity, ...]
    counts: jax.Array  # int32[n_shards], valid rows per shard
    capacity: int  # per-shard row capacity (static)
    mesh: object  # jax.sharding.Mesh
    # Host copy of counts, cached: every device_get is a blocking
    # driver<->device round trip, and the drivers of
    # count()/exchanges/collect all need counts. Builders that
    # know the counts (from_numpy, block_range, exchange drivers that
    # already fetched them with the overflow flag) pass them in; otherwise
    # the first counts_np fetches once.
    counts_host: Optional[np.ndarray] = None
    # Speculative blocks (dense_rdd deferred-overflow exchanges) carry a
    # settle callable: it batches every pending overflow-flag fetch into
    # one transfer and, on a failed speculation, repairs this block IN
    # PLACE (same object identity) from a clean re-materialization. Any
    # host-facing read must settle first — reading counts or columns of
    # an unsettled speculative block could observe capacity-truncated
    # data.
    settle: Optional[object] = None
    # Dictionary sidecar for string columns (tpu/dict_encoding.py):
    # {column name -> sorted host numpy array of dictionary values}, where
    # the column holds int32 codes indexing it. Host metadata only — never
    # shipped to device. None when no column is dictionary-encoded.
    dicts: Optional[Dict[str, np.ndarray]] = None
    # Multi-process only: replicated host copy of all columns, filled by
    # the first shard_rows (each host read there costs a full-block
    # all-gather; per-split consumption reads every shard).
    _host_cols_cache: Optional[Dict[str, np.ndarray]] = None

    def host_cols(self) -> Dict[str, np.ndarray]:
        """Replicated host copy of all columns, gathered once.

        The fill is serialized (double-checked lock): two scheduler task
        threads must not both dispatch the replicate-gather collective —
        in a multi-process mesh every process has to dispatch the same
        collectives in the same order, and a duplicated gather on one
        process deadlocks the others. DenseRDD.splits() pre-fills this on
        the driver thread before task fan-out for the same reason."""
        if self._host_cols_cache is None:
            with _host_cache_lock:
                if self._host_cols_cache is None:
                    self._host_cols_cache = {
                        name: np.asarray(c) for name, c in
                        # vegalint: ignore[VG003] — serializing this gather IS the point: a duplicated replicate-gather collective deadlocks multi-process meshes (docstring above)
                        mesh_lib.host_get(dict(self.cols)).items()}
        return self._host_cols_cache

    @property
    def n_shards(self) -> int:
        return self.mesh.size

    @property
    def counts_np(self) -> np.ndarray:
        if self.settle is not None:
            self.settle()  # may replace cols/counts/capacity in place
        if self.counts_host is None:
            self.counts_host = np.asarray(mesh_lib.host_get(self.counts))
        return self.counts_host

    @property
    def num_rows(self) -> int:
        return int(np.sum(self.counts_np))

    @property
    def column_names(self) -> List[str]:
        return list(self.cols)

    @property
    def nbytes(self) -> int:
        """Device-resident bytes of this block (all columns, full static
        capacity — padding rows occupy HBM like any others). HBM
        accounting for materialized blocks; pre-materialization sizing
        (which only has row counts) lives in stream.planned_chunk_rows."""
        return sum(int(np.prod(c.shape)) * c.dtype.itemsize
                   for c in self.cols.values())

    def _host_shards(self) -> Dict[str, List[np.ndarray]]:
        """{column: [shard s's `capacity` host rows, for every s]}, all
        columns in ONE transfer (a device_get per column is a blocking
        round trip each). A column laid out a shard a device is read as
        its shards' own host buffers, so nothing assembles a global host
        array only for to_numpy to slice it apart again; any other is
        fetched whole and sliced here, as views. Multi-process: share
        shard_rows' replicated cache — each miss is a full-block
        all-gather."""
        cap, n = self.capacity, self.n_shards
        first = next(iter(self.cols.values()), None)
        if isinstance(first, jax.Array) and not first.is_fully_addressable:
            fetched = self.host_cols()
        else:
            fetched = mesh_lib.host_get({
                name: _shard_buffers(col, cap) or col
                for name, col in self.cols.items()})
        return {name: got if isinstance(got, list) else
                [np.asarray(got)[s * cap:(s + 1) * cap] for s in range(n)]
                for name, got in fetched.items()}

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Gather valid rows to host, shard order preserved. Two-column
        int64 keys (KEY_LO) come back as one int64 KEY column — host-facing
        consumers never see the encoding. Every output column is allocated
        once at sum(counts) rows and each of its bytes written once: a
        plain column by slice assignment, a wide (name, name.lo) pair
        joined by decode_i64 straight into place. A result of
        _THREADED_FILL_BYTES or more is written on the decode pool, in runs
        of about _FILL_TASK_BYTES, so its fresh pages fault in side by side;
        a smaller one a shard at a time on the calling thread. Either way
        the bytes are the same."""
        counts = self.counts_np
        shards = self._host_shards()
        with spans.span("decode") as sp:
            total = int(np.sum(counts))
            out, fills = {}, []  # fills: (dst, its shards, its .lo's or None)
            for name, col in self.cols.items():
                if is_lo(name):
                    continue
                lo = shards.get(lo_of(name))
                out[name] = np.empty((total,) + col.shape[1:],
                                     col.dtype if lo is None else np.int64)
                fills.append((out[name], shards[name], lo))
            nbytes = sum(dst.nbytes for dst, _, _ in fills)
            if nbytes >= _THREADED_FILL_BYTES:
                spans.count("decode_threaded_bytes", nbytes)
                list(_decode_pool().map(
                    _fill, _fill_runs(fills, counts, _FILL_TASK_BYTES)))
            else:
                spans.count("decode_inline_bytes", nbytes)
                for run in _fill_runs(fills, counts):
                    _fill(run)
            decoded = _decode_dict_cols(out, self.dicts)
            sp.nbytes = sum(c.nbytes for c in decoded.values())
            return decoded

    def shard_rows(self, shard: int) -> Dict[str, np.ndarray]:
        counts = self.counts_np
        lo = shard * self.capacity
        c = int(counts[shard])
        first = next(iter(self.cols.values()), None)
        if isinstance(first, jax.Array) and not first.is_fully_addressable:
            # Eager slicing of a non-fully-addressable column is not
            # defined; fetch whole columns once (replicated all-gather),
            # cache them on the block — per-split host consumption calls
            # shard_rows n_shards times — and slice on host. The numpy
            # (_HostMeshStub) and single-process cases below never touch
            # jax.process_count(): a worker reading host numpy must not
            # initialize the backend (the driver process owns the chip).
            sliced = {name: np.asarray(col)[lo:lo + c]
                      for name, col in self.host_cols().items()}
        else:
            # Serialized: per-split host consumption runs on scheduler
            # task threads, and concurrent device slicing + device_get
            # from two threads deadlocks XLA:CPU's runtime on old jaxlibs
            # under --xla_force_host_platform_device_count on a 1-core
            # box (observed: one thread wedged dispatching the gather,
            # another inside device_get, 0% CPU). One lock here costs
            # nothing — the path is host-bound anyway — and removes the
            # interleaving entirely.
            with _host_cache_lock, mesh_lib.device_door(), \
                    spans.span("fetch") as sp:
                # vegalint: ignore[VG003] — serializing this device_get IS the fix: concurrent slice+device_get from two task threads deadlocks old XLA:CPU on 1 core (CLAUDE.md)
                sliced = jax.device_get(
                    {name: col[lo:lo + c] for name, col in self.cols.items()}
                )  # one transfer for all columns
                sp.nbytes = sum(a.nbytes for a in sliced.values())
        with spans.span("decode") as sp:
            decoded = _decode_dict_cols(
                _decode_key_cols(
                    {name: np.asarray(col) for name, col in sliced.items()}
                ),
                self.dicts,
            )
            sp.nbytes = sum(c.nbytes for c in decoded.values())
            return decoded


def _round_capacity(c: int) -> int:
    """Round per-shard capacity to a shape-stable bucket.

    Below 1M rows: next power of two (>=128) — few distinct shapes, so the
    structural program cache (dense_rdd.py) and XLA's jit cache stay hot
    across small pipelines. Above 1M: next multiple of 1M — pow2 would
    waste up to ~2x memory and sort work exactly where blocks are large
    (big jobs have few distinct shapes anyway). Both are multiples of 128
    (TPU lane width)."""
    c = max(c, 128)
    if c <= (1 << 20):
        return 1 << (c - 1).bit_length()
    step = 1 << 20
    return -(-c // step) * step


def _check_dtype(name: str, src: np.ndarray) -> np.ndarray:
    """Without jax x64, 64-bit inputs silently narrow to 32-bit on
    device_put. Narrowing int keys/values beyond int32 range would silently
    corrupt (key collisions, wrong sums) — refuse loudly; floats narrow with
    precision loss, which is the documented dtype contract."""
    import jax as _jax

    if src.dtype.kind in "OUS":
        # Strings were already dictionary-encoded upstream (from_numpy
        # runs encode_string_columns first), so anything still here is a
        # mixed-object column or a string column with encoding disabled.
        # jax.device_put would throw a raw TypeError — raise the crisp
        # VegaError instead so callers (RDD.dense, the frame planner)
        # degrade to the host tier.
        from vega_tpu.errors import VegaError

        raise VegaError(
            f"column {name!r} has dtype {src.dtype} which has no device "
            "representation (mixed Python objects, or strings with "
            "dense_dict_enabled=false) — use the host tier for this data."
        )
    if _jax.config.read("jax_enable_x64"):
        return src
    if src.dtype in (np.int64, np.uint64):
        narrow = np.uint32 if src.dtype == np.uint64 else np.int32
        info = np.iinfo(narrow)
        if len(src) and (src.min() < info.min or src.max() > info.max):
            from vega_tpu.errors import VegaError

            raise VegaError(
                f"column {name!r} has {src.dtype} values outside "
                f"{np.dtype(narrow)} range and jax x64 is disabled — values "
                "would silently collide. Enable x64 "
                "(jax.config.update('jax_enable_x64', True)) or use the "
                "host tier for this data."
            )
        return src.astype(narrow)
    if src.dtype == np.float64:
        return src.astype(np.float32)
    return src


def encode_key_columns(columns: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Split an int64 KEY column that does not fit int32 into the
    (KEY, KEY_LO) two-column encoding; in-range integer keys keep the
    cheaper single-column narrow path (block._check_dtype). Idempotent —
    already-encoded columns pass through (the streamed source pre-encodes
    on the FULL column so every chunk gets the same schema regardless of
    its local key range)."""
    if KEY_LO in columns:
        if KEY not in columns or \
                np.asarray(columns[KEY_LO]).dtype != np.int32:
            from vega_tpu.errors import VegaError

            raise VegaError(
                f"column name {KEY_LO!r} is reserved for the low word of "
                "two-column int64 keys"
            )
        return columns
    src = columns.get(KEY)
    if src is None:
        return columns
    src = np.asarray(src)
    if src.dtype not in (np.int64, np.uint64):
        return columns
    if len(src) == 0:
        return columns
    if src.dtype == np.uint64 and src.max() > np.uint64(2**63 - 1):
        from vega_tpu.errors import VegaError

        raise VegaError(
            "uint64 keys beyond int64 range are not representable on "
            "device — use the host tier for this data"
        )
    info = np.iinfo(np.int32)
    if info.min <= src.min() and src.max() <= info.max:
        return columns  # fits int32; _check_dtype narrows it
    hi, lo = encode_i64(src)
    out: Dict[str, np.ndarray] = {}
    for name, col in columns.items():
        if name == KEY:
            out[KEY] = hi
            out[KEY_LO] = lo
        else:
            out[name] = col
    return out


def encode_value_columns(columns: Dict[str, np.ndarray]
                         ) -> Dict[str, np.ndarray]:
    """Split int64 NON-key columns beyond int32 range into the wide
    (name, name.lo) encoding; in-range integers keep the narrow path.
    Idempotent like encode_key_columns (pre-encoded ".lo" columns pass
    through — the streamed source encodes ONCE on the full column so
    every chunk gets the same schema, then slices)."""
    out: Dict[str, np.ndarray] = {}
    for name, col in columns.items():
        if is_lo(name):
            out[name] = col  # pre-encoded (streamed chunks)
            continue
        src = np.asarray(col)
        if name == KEY or src.dtype not in (np.int64, np.uint64):
            out[name] = col
            continue
        if src.dtype == np.uint64 and len(src) and \
                src.max() > np.uint64(2**63 - 1):
            from vega_tpu.errors import VegaError

            raise VegaError(
                f"uint64 column {name!r} beyond int64 range is not "
                "representable on device — use the host tier"
            )
        info = np.iinfo(np.int32)
        in_range = (len(src) == 0
                    or (info.min <= src.min() and src.max() <= info.max))
        if in_range:
            out[name] = col  # fits int32; _check_dtype narrows it
            continue
        hi, lo = encode_i64(src)
        out[name] = hi
        out[lo_of(name)] = lo
    return out


@dataclasses.dataclass(frozen=True)
class HostBlock:
    """A Block laid out on the host, ready to put: each column narrowed and
    padded to `n_shards * capacity` rows, and the shards' row counts."""

    cols: Dict[str, np.ndarray]
    counts: np.ndarray
    capacity: int
    dicts: Optional[Dict[str, np.ndarray]]


def from_numpy(columns: Dict[str, np.ndarray], mesh=None,
               capacity: Optional[int] = None,
               wide_values: bool = True,
               dicts: Optional[Dict[str, np.ndarray]] = None) -> Block:
    """Build a row-sharded Block from host columns: encode_numpy (the host's
    side, in `encode` spans), then put_host_block (the `put`s)."""
    mesh = mesh or mesh_lib.default_mesh()
    return put_host_block(
        encode_numpy(columns, mesh, capacity, wide_values, dicts), mesh)


def put_host_block(host: HostBlock, mesh=None) -> Block:
    """The device's side of from_numpy: one `put` a column and one for the
    counts. A put returns before its transfer ends."""
    mesh = mesh or mesh_lib.default_mesh()
    spec = mesh_lib.shard_spec(mesh)
    cols = {name: mesh_lib.host_put(col, spec)
            for name, col in host.cols.items()}
    return Block(cols=cols, counts=mesh_lib.host_put(host.counts, spec),
                 capacity=host.capacity, mesh=mesh, counts_host=host.counts,
                 dicts=host.dicts)


def encode_numpy(columns: Dict[str, np.ndarray], mesh=None,
                 capacity: Optional[int] = None,
                 wide_values: bool = True,
                 dicts: Optional[Dict[str, np.ndarray]] = None) -> HostBlock:
    """The host's side of from_numpy (equal-length columns). int64
    columns beyond int32 range are transparently stored as two-column
    (name, name.lo) encodings (see LO_SUFFIX above) — the KEY via
    encode_key_columns, value columns via encode_value_columns (unless
    wide_values=False, for layouts with no wide form: the caller then
    degrades to the host tier on the VegaError _check_dtype raises).
    String columns dictionary-encode into int32 codes plus a dicts
    sidecar (tpu/dict_encoding.py); pre-encoded callers (parquet
    dictionary pages, streamed chunks) pass the code columns plus their
    `dicts` directly. With dense_dict_enabled off, strings raise the same
    crisp VegaError — callers degrade to the host tier."""
    from vega_tpu.tpu import dict_encoding

    mesh = mesh or mesh_lib.default_mesh()
    n_shards = mesh.size
    # Strings first: their codes are plain int32 columns for the int64
    # wide encodes below (which pass them through untouched).
    columns, dicts = dict_encoding.encode_string_columns(
        dict(columns), dicts)
    columns = encode_key_columns(columns)
    if wide_values:
        columns = encode_value_columns(columns)
    names = list(columns)
    n = len(columns[names[0]]) if names else 0
    per = -(-n // n_shards) if n else 0
    cap = _round_capacity(capacity or max(per, 1))
    counts = np.zeros(n_shards, dtype=np.int32)
    cols = {}
    for name in names:
        # the host's side of the feed; put_host_block's `put`s follow it
        with spans.span("encode") as sp:
            src = _check_dtype(name, np.asarray(columns[name]))
            if n == n_shards * cap:
                # every shard full: the padded layout is the column itself
                # (device_put copies it; a streamed chunk is a slice)
                dst = np.ascontiguousarray(src)
                counts[:] = cap
            else:
                dst = np.zeros((n_shards * cap,) + src.shape[1:],
                               dtype=src.dtype)
                for s in range(n_shards):
                    lo, hi = s * per, min((s + 1) * per, n)
                    c = max(0, hi - lo)
                    counts[s] = c
                    if c:
                        dst[s * cap:s * cap + c] = src[lo:hi]
            sp.nbytes = dst.nbytes
        cols[name] = dst
    return HostBlock(cols=cols, counts=counts, capacity=cap, dicts=dicts)


def block_range(n: int, mesh=None, dtype=jnp.int32, start: int = 0) -> Block:
    """Lazy iota block: shard s holds [start+s*per, start+s*per+count_s) —
    the device analogue of ctx.range (reference: context.rs:422-442), built
    on device with no host materialization. `start` offsets the whole range
    (used by the chunked/streamed source)."""
    from jax.sharding import PartitionSpec as P

    mesh = mesh or mesh_lib.default_mesh()
    n_shards = mesh.size
    per = -(-n // n_shards)
    cap = _round_capacity(per)
    counts_host = np.array(
        [max(0, min(per, n - s * per)) for s in range(n_shards)],
        dtype=np.int32,
    )

    def build():
        # axis_index instead of a device_put'd shard-id input: keeps the
        # source fully device-built and multiprocess-safe (no host array
        # to place on non-addressable devices).
        base = start + jax.lax.axis_index(mesh_lib.SHARD_AXIS) * per
        return base + jax.lax.iota(dtype, cap)

    from vega_tpu.tpu import compat

    build_sharded = jax.jit(
        compat.shard_map(
            build, mesh=mesh, in_specs=(),
            out_specs=P(mesh_lib.SHARD_AXIS),
        )
    )
    vals = build_sharded()
    counts = mesh_lib.host_put(counts_host, mesh_lib.shard_spec(mesh))
    return Block(cols={VALUE: vals}, counts=counts, capacity=cap, mesh=mesh,
                 counts_host=counts_host)


def single_column(values, mesh=None) -> Block:
    # Keyless int64 columns beyond int32 range use the wide (VALUE,
    # VALUE.lo) encoding like every other column: named reductions fold
    # the pair on device (dense_rdd._named_reduce_wide) and row-wise
    # closures fall back to the host tier, which sees decoded int64s.
    return from_numpy({VALUE: np.asarray(values)}, mesh)


def pair_block(keys, values, mesh=None) -> Block:
    return from_numpy({KEY: np.asarray(keys), VALUE: np.asarray(values)}, mesh)
