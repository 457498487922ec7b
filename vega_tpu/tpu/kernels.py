"""Shard-local device algorithms for the dense tier.

These functions run *inside* jax.shard_map over the "shards" mesh axis: every
array is the per-shard view ([capacity, ...] columns, int32[1] count). They
replace the reference's shuffle planes with XLA-native equivalents
(SURVEY.md §7):

  reference map-side combine (dependency.rs:164-229)  -> bucket_by_hash + local segment pre-reduce
  HTTP pull shuffle (shuffle_manager.rs/shuffle_fetcher.rs) -> lax.all_to_all over ICI
  reduce-side merge (shuffled_rdd.rs:149-170)          -> sort + segment reduction
  cogroup/join merge (co_grouped_rdd.rs:206-249)       -> sort-merge join

Everything is static-shape: raggedness is (count, validity-mask), never a
dynamic dimension (SURVEY.md §7 hard part 1). Capacity overflow is detected
on device and surfaced as a flag the driver checks, then retries with a
larger capacity (the moral equivalent of MoE capacity-factor overflow).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from vega_tpu.tpu.mesh import SHARD_AXIS
from vega_tpu.tpu.spans import stage

Cols = Dict[str, jax.Array]

# ---------------------------------------------------------------------------
# hashing / masks / compaction
# ---------------------------------------------------------------------------


def hash32(col: jax.Array) -> jax.Array:
    """lowbias32 finalizer over a column's bit pattern (device analogue of
    partitioner.hash_key; 32-bit because TPUs have no native int64).

    Bucket placement need not match the host tier bit-for-bit — only final
    RDD *results* must match (BASELINE.md parity) — so the device tier uses
    the cheapest good mixer."""
    if col.dtype in (jnp.float32,):
        x = lax.bitcast_convert_type(col, jnp.uint32)
    elif col.dtype in (jnp.float64, jnp.int64, jnp.uint64):
        x64 = lax.bitcast_convert_type(col.astype(jnp.float64), jnp.uint64) \
            if jnp.issubdtype(col.dtype, jnp.floating) else col.astype(jnp.uint64)
        x = (x64 ^ (x64 >> jnp.uint64(32))).astype(jnp.uint32)
    else:
        x = col.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def hash32_pair(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Mix two 32-bit words into one 32-bit hash (the bucket hash for
    (hi, lo)-encoded int64 keys, block.py KEY_LO). hash-combine of the two
    lowbias32 digests followed by one more finalizer round; like hash32,
    only bucket placement depends on it, so any good mixer is valid."""
    a = hash32(hi)
    b = hash32(lo)
    x = a ^ (b + jnp.uint32(0x9E3779B9) + (a << jnp.uint32(6))
             + (a >> jnp.uint32(2)))
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    return x


def searchsorted2(rh: jax.Array, rl: jax.Array, qh: jax.Array,
                  ql: jax.Array, side: str = "left") -> jax.Array:
    """Vectorized lexicographic searchsorted over two-word keys: positions
    of queries (qh, ql) in rows (rh, rl) sorted by (rh major, rl minor).
    jnp.searchsorted cannot compare composite keys, so this is the classic
    branchless binary search unrolled to ceil(log2(n))+1 rounds — O(log n)
    vectorized gathers, no data-dependent control flow (jit-safe)."""
    n = rh.shape[0]
    lo = jnp.zeros(qh.shape, jnp.int32)
    hi = jnp.full(qh.shape, n, jnp.int32)
    for _ in range(max(1, int(n).bit_length())):
        mid = (lo + hi) >> 1
        safe = jnp.clip(mid, 0, max(n - 1, 0))
        mh = jnp.take(rh, safe)
        ml = jnp.take(rl, safe)
        if side == "left":
            go = (mh < qh) | ((mh == qh) & (ml < ql))
        else:
            go = (mh < qh) | ((mh == qh) & (ml <= ql))
        active = lo < hi
        lo = jnp.where(active & go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)
    return lo


def valid_mask(capacity: int, count: jax.Array) -> jax.Array:
    return lax.iota(jnp.int32, capacity) < count


def compact(cols: Cols, keep: jax.Array, out_capacity: int) -> Tuple[Cols, jax.Array]:
    """Move rows where keep=True to the front; returns (cols, new_count).
    Stable (a kept row lands at its exclusive prefix count), static-shape,
    zeros after the kept rows: a cumsum and one scatter a column. Whole rows
    go through it after every exchange that moves rows (the received rows),
    and in filter, sample, flat_map and union; the named segment reduce sends
    its key words alone, the scanned one its rows. Where the mask is a prefix
    no row moves and nothing is scattered: passthrough_exchange."""
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    idx = jnp.where(keep, pos, out_capacity)  # dropped rows land out of range
    out = {}
    for n, c in cols.items():
        dst = jnp.zeros((out_capacity,) + c.shape[1:], c.dtype)
        out[n] = dst.at[idx].set(c, mode="drop")
    return out, jnp.sum(keep).astype(jnp.int32)


@stage("key_sort")
def sort_carrying(keys, cols: Cols):
    """One stable lax.sort by `keys` (a tuple of 1-D key words, major first)
    that moves the rows of `cols` with them. Returns (sorted keys, cols).

    Every 1-D column is a further operand of the sort, so it arrives in
    order with the keys: no permutation is sorted and no column is gathered
    through one. lax.sort takes operands of one shape only, so where a
    column has more than one dimension an iota rides too and those columns
    alone are gathered through it: decided from the columns' rank, at trace
    time.

    No cap on the operands. On a v5e at 64Mi rows (PERF.md, PR 33) a 32-bit
    operand adds 0.081 s to the sort's run and 13.4 s to its compile, both
    in proportion up to the eight measured (one key and eight columns:
    0.82 s to run, 124 s to compile), where the gather it replaces runs
    0.98 s an action and compiles in none (sorting an iota and gathering
    eight columns: 8.97 s, 22 s): a program run fifteen times has its
    compile back, and the compile cache keeps it."""
    flat = {n: c for n, c in cols.items() if c.ndim == 1}
    deep = {n: c for n, c in cols.items() if c.ndim > 1}
    operands = (*keys, *flat.values())
    if deep:
        operands += (lax.iota(jnp.int32, keys[0].shape[0]),)
    res = lax.sort(operands, num_keys=len(keys), is_stable=True)
    out = dict(zip(flat, res[len(keys):]))
    out.update((n, jnp.take(c, res[-1], axis=0)) for n, c in deep.items())
    return res[:len(keys)], out


# ---------------------------------------------------------------------------
# exchange: the device shuffle
# ---------------------------------------------------------------------------


@stage("exchange_compact")
def passthrough_exchange(cols: Cols, count: jax.Array, capacity: int,
                         out_capacity: int):
    """Single-shard fast path shared by every exchange implementation: the
    bucket/sort/collective is a no-op; just re-capacity the block.

    No row moves: the valid rows are the prefix [0, count), so each column
    is sliced or zero-padded to out_capacity (decided at trace time from the
    two static capacities) and one select zeroes the slots past the kept
    rows. Bit for bit what compact(cols, valid_mask(capacity, count),
    out_capacity) gives (kept rows first in their order, zeros after them,
    the rows past out_capacity cut on overflow) without its cumsum and
    scatter, which the chip runs as a sort and a fusion over every slot
    (0.49 s a 64Mi-slot column: PERF.md, PR 39)."""
    new_count = jnp.clip(count, 0, capacity).astype(jnp.int32)
    kept = valid_mask(out_capacity, new_count)
    out = {}
    for n, c in cols.items():
        if capacity >= out_capacity:
            c = c[:out_capacity]
        else:
            c = jnp.pad(c, [(0, out_capacity - capacity)]
                        + [(0, 0)] * (c.ndim - 1))
        out[n] = jnp.where(kept.reshape((-1,) + (1,) * (c.ndim - 1)), c,
                           jnp.zeros((), c.dtype))
    return out, new_count, new_count > out_capacity


@stage("exchange_group")
def _group_by_bucket(cols: Cols, bucket: jax.Array, n_shards: int,
                     prefer_low_memory: bool = False):
    """Stable-group rows by target bucket; returns (grouped cols,
    per-bucket counts, per-bucket start offsets).

    Bucket ids live in the tiny range [0, n_shards] — for small meshes a
    counting sort (one-hot prefix counts + one scatter per column, O(n*k))
    beats the O(n log n) argsort. The one-hot/cumsum intermediates are
    O(capacity * n_shards), so callers with a memory bound to honor
    (ring_exchange) set prefer_low_memory and larger meshes always take the
    argsort path."""
    from vega_tpu.tpu import pallas_kernels as _pk

    counts_all = _pk.bucket_hist(bucket, n_shards + 1)
    counts_to = counts_all[:n_shards]
    starts_all = jnp.cumsum(counts_all) - counts_all  # exclusive prefix
    starts = starts_all[:n_shards]
    if n_shards <= 64:
        from vega_tpu.tpu import pallas_kernels

        capacity = bucket.shape[0]
        # Platform-selected ranks (lax.platform_dependent): TPU streams
        # the bucket column once through the Pallas kernel (VMEM tile
        # ranks + SMEM per-bucket carries — O(capacity) HBM, so even
        # memory-bounded callers like ring_exchange use it); elsewhere
        # the XLA one-hot path, or the argsort path when
        # prefer_low_memory (the one-hot's O(capacity * n_shards)
        # intermediates are what that flag exists to avoid).
        pos = pallas_kernels.partition_pos(
            bucket, n_shards + 1, starts_all,
            prefer_low_memory=prefer_low_memory)
        if pos is not None:
            grouped = {}
            for name, col in cols.items():
                dst = jnp.zeros((capacity,) + col.shape[1:], col.dtype)
                grouped[name] = dst.at[pos].set(col, mode="drop")
            return grouped, counts_to, starts
    # Escape hatch (>64 buckets, or low-memory without the Pallas path):
    # a stable sort by bucket that carries the columns. Every row
    # participates; padding rows carry bucket == n_shards and sort last by
    # value.
    _, grouped = sort_carrying((bucket,), cols)
    return grouped, counts_to, starts


def bucket_key_sort(cols: Cols, bucket: jax.Array, key_name: str,
                    lo_name: str = None) -> Tuple[Cols, jax.Array]:
    """One stable multi-key sort by (bucket major, key minor).

    Rows become bucket-grouped with a key-sorted run per bucket, so a single
    lax.sort feeds BOTH the presorted map-side combine and a pregrouped
    exchange — replacing the separate pre-combine key sort and the
    exchange's bucket grouping (the 3-sorts-to-2 restructuring of the
    reference's map-side combine, dependency.rs:176-223). Caller must have
    ghosted invalid rows (bucket = n_shards) so they sink to the end.
    lo_name names the low word of a two-column int64 key (block.py KEY_LO):
    it joins the sort keys so runs are sorted by the full 64-bit key.
    Returns (cols, bucket), both in the sorted order: every row, ghosts
    included, keeps all its columns (the keys are sorted as they are, the
    other columns ride the sort, sort_carrying)."""
    names = [key_name] if lo_name is None else [key_name, lo_name]
    (sorted_bucket, *words), out = sort_carrying(
        (bucket, *(cols[n] for n in names)),
        {n: c for n, c in cols.items() if n not in names})
    out.update(zip(names, words))
    return out, sorted_bucket


@stage("exchange_group")
def range_bucket(bounds: jax.Array, keys: jax.Array,
                 ascending: bool, bounds_lo: jax.Array = None,
                 keys_lo: jax.Array = None) -> jax.Array:
    """Range-partition bucket ids from sorted split bounds (sort_by_key's
    partitioner). Shared by the exchange program and its sizing histogram —
    exact capacity sizing depends on the two staying bit-identical.
    (bounds_lo, keys_lo) carry the low word of two-column int64 keys."""
    if bounds_lo is None:
        if ascending:
            return jnp.searchsorted(bounds, keys).astype(jnp.int32)
        if jnp.issubdtype(keys.dtype, jnp.floating):
            return jnp.searchsorted(-bounds, -keys).astype(jnp.int32)
        # bitwise-not, not negation: -INT32_MIN wraps onto itself and
        # lands the most negative key in the first (largest) bucket
        return jnp.searchsorted(~bounds, ~keys).astype(jnp.int32)
    if not ascending:
        # bitwise-not is order-reversing for int32 with no INT_MIN
        # negation overflow; applied to both words it reverses the
        # lexicographic order.
        bounds, bounds_lo = ~bounds, ~bounds_lo
        keys, keys_lo = ~keys, ~keys_lo
    return searchsorted2(bounds, bounds_lo, keys, keys_lo).astype(jnp.int32)


@stage("exchange_group")
def pregrouped_group(bucket: jax.Array, n_shards: int):
    """(counts_to, starts) for rows already contiguous per bucket — the
    histogram shortcut both exchanges use instead of _group_by_bucket."""
    from vega_tpu.tpu import pallas_kernels as _pk

    counts_all = _pk.bucket_hist(bucket, n_shards + 1)
    counts_to = counts_all[:n_shards]
    starts = (jnp.cumsum(counts_all) - counts_all)[:n_shards]
    return counts_to, starts


@stage("exchange_send")
def send_slots(col: jax.Array, starts: jax.Array, counts: jax.Array,
               slot_capacity: int) -> jax.Array:
    """The send buffer of one column, [len(starts), slot_capacity, ...]:
    slot b holds the first slot_capacity rows of the run [starts[b],
    starts[b] + counts[b]) of rows grouped by destination, zeros after them.

    A run is contiguous, so each slot is one lax.dynamic_slice at its start
    (len(starts) is static) and no row is gathered through an index array:
    on a v5e a 64Mi-row column's four slots of 17Mi rows take about 2 ms,
    where a take through starts[b] + iota took 1.8 s (PERF.md §6).
    dynamic_slice clamps a start that would run past the column, which
    would shift the run; so the slices are taken from the column extended
    by slot_capacity zero rows (the chip's compiler fuses the pad into the
    slices), where every start the grouping gives (at most the column's
    length) is in bounds, whatever slot_capacity is. A select on each slice
    zeroes its rows past counts[b]. The buffer is bit for bit that take,
    clipped to the column and zeroed past counts[b]: a run longer than
    slot_capacity sends its first slot_capacity rows, and the caller flags
    the overflow."""
    padded = jnp.pad(col, [(0, slot_capacity)] + [(0, 0)] * (col.ndim - 1))
    row = lax.iota(jnp.int32, slot_capacity).reshape(
        (slot_capacity,) + (1,) * (col.ndim - 1))
    return jnp.stack([
        jnp.where(row < counts[b],
                  lax.dynamic_slice_in_dim(padded, starts[b], slot_capacity),
                  jnp.zeros((), col.dtype))
        for b in range(starts.shape[0])])


def bucket_exchange(
    cols: Cols,
    count: jax.Array,  # int32[] per-shard valid count
    bucket: jax.Array,  # int32[capacity] target shard per row
    n_shards: int,
    slot_capacity: int,  # C: max rows this shard sends to any one target
    out_capacity: int,  # per-shard capacity of the received block
    pregrouped: bool = False,  # rows already bucket-grouped (bucket_key_sort)
) -> Tuple[Cols, jax.Array, jax.Array]:
    """All-to-all by bucket id. Returns (cols, new_count, overflow_flag).

    Map side: group rows by target bucket, then build each column's
    [n_shards, slot_capacity] send buffer from one dynamic slice a
    destination (send_slots). Wire: one lax.all_to_all per column over ICI.
    Reduce side: mask + compact received rows. This is the entire reference
    shuffle data plane (SURVEY.md §2.5) as one fused XLA program.

    With pregrouped=True the caller guarantees valid rows are already
    contiguous per target bucket (e.g. via bucket_key_sort) and the grouping
    pass collapses to a bincount."""
    capacity = bucket.shape[0]
    if n_shards == 1:
        return passthrough_exchange(cols, count, capacity, out_capacity)
    with stage("exchange_group"):
        mask = valid_mask(capacity, count)
        bucket = jnp.where(mask, bucket, n_shards)  # invalid rows -> ghost

        if pregrouped:
            counts_to, starts = pregrouped_group(bucket, n_shards)
            sorted_cols = cols
        else:
            sorted_cols, counts_to, starts = _group_by_bucket(
                cols, bucket, n_shards)
        overflow_send = jnp.any(counts_to > slot_capacity)

    with stage("exchange_send"):
        send_counts = jnp.minimum(counts_to, slot_capacity).astype(jnp.int32)
    with stage("exchange_wire"):
        recv_counts = lax.all_to_all(
            send_counts, SHARD_AXIS, split_axis=0, concat_axis=0
        )

    received: Cols = {}
    for name, col in sorted_cols.items():
        buf = send_slots(col, starts, counts_to, slot_capacity)
        with stage("exchange_wire"):
            got = lax.all_to_all(buf, SHARD_AXIS, split_axis=0,
                                 concat_axis=0)
            received[name] = got.reshape(
                (n_shards * slot_capacity,) + got.shape[2:])

    with stage("exchange_compact"):
        recv_valid = (
            jnp.arange(slot_capacity)[None, :] < recv_counts[:, None]
        ).reshape(-1)
        new_count = jnp.sum(recv_counts).astype(jnp.int32)
        overflow_recv = new_count > out_capacity
        out_cols, _ = compact(received, recv_valid, out_capacity)
        return out_cols, new_count, overflow_send | overflow_recv


# ---------------------------------------------------------------------------
# sorted-run segment operations (the reduce side)
# ---------------------------------------------------------------------------


@stage("key_sort")
def sort_by_column(cols: Cols, count: jax.Array, key_name: str,
                   descending: bool = False, lo_name: str = None) -> Cols:
    """Stable sort valid rows by one column (or a (key, lo) two-column
    int64 key when lo_name is given); invalid rows sink to the end.

    The key words are the keys of one lax.sort and every other column rides
    it (sort_carrying). Rows past `count` keep their own order and their
    other columns, but not their key: the sort orders them by the padding
    they are forced to, and that is what their key words hold afterwards
    (the dtype's largest value, +inf for floats; descending its flip, the
    smallest value, -inf). Ascending, each whole key column is therefore
    sorted, padding included. Every reader masks by `count`."""
    names = [key_name] if lo_name is None else [key_name, lo_name]
    mask = valid_mask(cols[key_name].shape[0], count)

    def flip(w):
        # bitwise-not is the overflow-free order flip for ints (negation
        # wraps INT32_MIN onto itself and mis-sorts it first), word by word
        # the flip of the lexicographic order too; floats negate exactly.
        # Each is its own inverse, bit for bit.
        if not descending:
            return w
        return -w if jnp.issubdtype(w.dtype, jnp.floating) else ~w

    words, out = sort_carrying(
        tuple(jnp.where(mask, flip(cols[n]), _orderable_max(cols[n]))
              for n in names),
        {n: c for n, c in cols.items() if n not in names})
    out.update((n, flip(w)) for n, w in zip(names, words))
    return out


_WIDE_BIAS = 0x80000000  # sign-flip bias on stored low words (block._LO_BIAS)


def _wide_unbias(lo: jax.Array) -> jax.Array:
    """Stored (biased int32) low word -> true unsigned low word."""
    return lax.bitcast_convert_type(lo, jnp.uint32) ^ jnp.uint32(_WIDE_BIAS)


def _wide_rebias(lo_u: jax.Array) -> jax.Array:
    return lax.bitcast_convert_type(lo_u ^ jnp.uint32(_WIDE_BIAS), jnp.int32)


def wide_add(a_hi, a_lo, b_hi, b_lo):
    """int64 addition over the wide (hi int32, biased-lo int32) encoding:
    unsigned low-word add with carry into the high word. Wraps mod 2^64
    like numpy int64 (the host tier's python ints are exact bignums —
    the documented device dtype contract)."""
    au, bu = _wide_unbias(a_lo), _wide_unbias(b_lo)
    s = au + bu  # uint32 wrap
    carry = (s < au).astype(jnp.int32)
    return a_hi + b_hi + carry, _wide_rebias(s)


def wide_add_checked(a_hi, a_lo, b_hi, b_lo):
    """wide_add plus a signed-overflow predicate: operands of equal sign
    whose sum's sign differs wrapped past the int64 range. The final
    mod-2^64 value is still exact whenever the TRUE total fits int64, so a
    sticky OR of these per-pair flags through a reduction is a conservative
    "total may be out of range" detector (false positives possible under
    reassociation; never false negatives)."""
    au, bu = _wide_unbias(a_lo), _wide_unbias(b_lo)
    s = au + bu  # uint32 wrap
    carry = (s < au).astype(jnp.int32)
    r_hi = a_hi + b_hi + carry
    same_sign = (a_hi < 0) == (b_hi < 0)
    ovf = same_sign & ((r_hi < 0) != (a_hi < 0))
    return r_hi, _wide_rebias(s), ovf


def wide_select(a_hi, a_lo, b_hi, b_lo, take_min: bool):
    """Lexicographic (hi, biased-lo) min/max — signed compares equal
    int64 order by construction of the encoding."""
    a_less = (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))
    pick_a = a_less if take_min else ~a_less
    return (jnp.where(pick_a, a_hi, b_hi), jnp.where(pick_a, a_lo, b_lo))


def _orderable_max(key: jax.Array):
    if jnp.issubdtype(key.dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype=key.dtype)
    return jnp.array(jnp.iinfo(key.dtype).max, dtype=key.dtype)


@stage("segment_reduce")
def segment_reduce_sorted(
    cols: Cols,
    count: jax.Array,
    key_name: str,
    combine: Callable,  # (value_cols_a, value_cols_b) -> value_cols
    presorted: bool = False,
    lo_name: str = None,
) -> Tuple[Cols, jax.Array]:
    """Generic reduce_by_key over a shard: sort by key, then a segmented
    associative scan with an arbitrary traceable combiner; the last row of
    each segment carries the reduction. Returns compacted (cols, count).
    lo_name names the low word of a two-column int64 key: it sorts and
    segments with the key and rides to the output untouched.

    This is reference hot loop 2 (shuffled_rdd.rs:154-164 merge_combiners
    into a HashMap) recast as sort + scan so it vectorizes on the VPU instead
    of chasing hash buckets."""
    capacity = cols[key_name].shape[0]
    if not presorted:
        cols = sort_by_column(cols, count, key_name, lo_name=lo_name)
    mask = valid_mask(capacity, count)
    keys = cols[key_name]
    first = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        keys[1:] != keys[:-1],
    ])
    if lo_name is not None:
        lo_col = cols[lo_name]
        first = first | jnp.concatenate([
            jnp.ones((1,), jnp.bool_), lo_col[1:] != lo_col[:-1],
        ])
    key_set = {key_name} if lo_name is None else {key_name, lo_name}
    value_cols = {n: c for n, c in cols.items() if n not in key_set}

    def seg_combine(a, b):
        va, fa = a
        vb, fb = b
        merged = combine(va, vb)
        out = jax.tree.map(
            lambda m, y: jnp.where(
                fb.reshape(fb.shape + (1,) * (m.ndim - 1)), y, m
            ),
            merged, vb,
        )
        return out, fa | fb

    scanned, _ = lax.associative_scan(seg_combine, (value_cols, first))
    # Segment end = next row starts a new segment, or this is the last valid row.
    idx = lax.iota(jnp.int32, capacity)
    next_first = jnp.concatenate([first[1:], jnp.ones((1,), jnp.bool_)])
    is_end = mask & (next_first | (idx == count - 1))
    out = dict(scanned)
    out[key_name] = keys
    if lo_name is not None:
        out[lo_name] = cols[lo_name]
    return compact(out, is_end, capacity)


_FAST_SEGMENT_OPS = {
    "add": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
    "prod": jax.ops.segment_prod,
}


# A float `add` takes the blocked path where a key has more rows than this
# in the sorted shard; a shorter run is added in turn, as ever: at most this
# many roundings.
LONG_RUN_ROWS = 4096


def _segment_totals_blocked(vals: jax.Array, first: jax.Array,
                            block: int) -> jax.Array:
    """Each segment's sum at its last row and 0 elsewhere, added pairwise so
    that the error grows with the logarithm of a segment's rows, not with
    the rows. `first` flags the rows that start a segment; row 0 starts one.

    Two levels of segmented scan, both dense: inside blocks of `block` rows
    (log2(block) shifted adds along the block), then over the blocks' trailing
    pieces (one carry a block, an associative scan over capacity / block
    elements), which a row takes only where its segment began in an earlier
    block."""
    n = vals.shape[0]
    pad = -n % block
    v = jnp.pad(vals, (0, pad)).reshape(-1, block)
    f = jnp.pad(first, (0, pad)).reshape(-1, block)
    ends = jnp.concatenate([first[1:], jnp.ones((1,), jnp.bool_)])
    d = 1
    while d < block:
        v = v + jnp.where(f, 0, jnp.pad(v[:, :-d], ((0, 0), (d, 0))))
        f = f | jnp.pad(f[:, :-d], ((0, 0), (d, 0)))
        d *= 2

    def carry(a, b):
        (va, fa), (vb, fb) = a, b
        return jnp.where(fb, vb, va + vb), fa | fb

    through, _ = lax.associative_scan(carry, (v[:, -1], f[:, -1]))
    carry_in = jnp.concatenate([jnp.zeros((1,), v.dtype), through[:-1]])
    v = v + jnp.where(f, 0, carry_in[:, None])
    return jnp.where(ends, v.reshape(-1)[:n], 0)


@stage("segment_reduce")
def segment_reduce_named(
    cols: Cols, count: jax.Array, key_name: str, op: str,
    presorted: bool = False, lo_name: str = None,
) -> Tuple[Cols, jax.Array]:
    """Fast path for the common monoids via XLA segment ops. lo_name names
    the low word of a two-column int64 key (sorts/segments with the key).

    Every output column is written once, at the row of its segment's id
    (segments are numbered in key order, from 0): a value column by the
    segment op's one scatter, which leaves segment i's reduction at row i;
    a key word by one compact of the rows that start a segment, which
    leaves the i-th segment's key at row i. Nothing is gathered and no row
    moves twice. Rows from n_segments on are zero in every column (a min,
    max or prod leaves its identity in the segments no row reached, and
    the padding rows' reduction at row capacity - 1: both are cleared).
    Returns (cols, n_segments), the value columns first."""
    seg_op = _FAST_SEGMENT_OPS[op]
    capacity = cols[key_name].shape[0]
    if not presorted:
        cols = sort_by_column(cols, count, key_name, lo_name=lo_name)
    mask = valid_mask(capacity, count)
    keys = cols[key_name]
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), keys[1:] != keys[:-1]]
    )
    if lo_name is not None:
        lo_col = cols[lo_name]
        first = first | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), lo_col[1:] != lo_col[:-1]]
        )
    first = first & mask
    seg_ids = jnp.cumsum(first.astype(jnp.int32)) - 1
    seg_ids = jnp.where(mask, seg_ids, capacity - 1)
    key_names = [key_name] if lo_name is None else [key_name, lo_name]
    masked_cols: Cols = {}
    for name, col in cols.items():
        if name in key_names:
            continue
        if op == "add" or op == "prod":
            neutral = jnp.zeros((), col.dtype) if op == "add" else jnp.ones((), col.dtype)
            masked_cols[name] = jnp.where(
                mask.reshape(mask.shape + (1,) * (col.ndim - 1)), col, neutral
            )
        else:
            masked_cols[name] = col
    # The scatter-add takes a segment's rows in turn: over a key of millions
    # of rows a float sum drifts by 1e-4. Where the sorted keys show a run
    # longer than LONG_RUN_ROWS, float columns are summed pairwise first and
    # the scatter adds one total a segment to zeros; elsewhere (and for every
    # shard too small to hold such a run) nothing changes.
    long_add = [name for name, col in masked_cols.items()
                if col.ndim == 1 and jnp.issubdtype(col.dtype, jnp.floating)
                ] if op == "add" and capacity > LONG_RUN_ROWS else []
    if long_add:
        far = keys[LONG_RUN_ROWS:] == keys[:-LONG_RUN_ROWS]
        if lo_name is not None:
            far = far & (lo_col[LONG_RUN_ROWS:] == lo_col[:-LONG_RUN_ROWS])

        # The rows past `count` are one more segment, of zeros. (Computed out
        # here: inside the branch the chip's program ran 0.09 s longer.)
        starts = first | (lax.iota(jnp.int32, capacity) == count)
        totals = lax.cond(
            jnp.any(far & mask[LONG_RUN_ROWS:]),
            lambda vals: [_segment_totals_blocked(v, starts, LONG_RUN_ROWS)
                          for v in vals],
            lambda vals: vals,
            [masked_cols[name] for name in long_add])
        masked_cols.update(zip(long_add, totals))
    # Key of segment i = key at the i-th segment start: the start rows'
    # exclusive prefix count is seg_ids there, so this compact puts it at row i.
    seg_keys, n_segments = compact(
        {name: cols[name] for name in key_names}, first, capacity)
    seg_valid = valid_mask(capacity, n_segments)
    out: Cols = {}
    for name, col in masked_cols.items():
        red = seg_op(col, seg_ids, num_segments=capacity)
        out[name] = jnp.where(
            seg_valid.reshape(seg_valid.shape + (1,) * (red.ndim - 1)),
            red, jnp.zeros((), red.dtype))
    out.update(seg_keys)
    return out, n_segments


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------


def ragged_expand(counts_per_row: jax.Array, out_capacity: int):
    """Slot ownership for ragged expansion: row i emits counts_per_row[i]
    contiguous output slots. Returns (owner, offset, total) where output
    slot j belongs to row owner[j] at position offset[j] within that row's
    run, and total is the exact output size (saturated to INT32_MAX if the
    int32 prefix sums wrapped — the caller must fail loudly, not truncate).
    The starts are a running sum, so no slot searches for its row: every
    row that emits something marks its first slot (one scatter of the
    rows), and two running maxima over the slots carry the mark and the
    marked slot's own number over the row's run. Rows with count 0 mark
    nothing and never own a slot. Slots at or past total are not output;
    their owner stays in range. Shared by merge_join_expand, the device
    flat_map and the cartesian product."""
    n_rows = counts_per_row.shape[0]
    m = counts_per_row
    starts = jnp.cumsum(m) - m
    total = jnp.sum(m).astype(jnp.int32)
    wrapped = (total < 0) | jnp.any(starts < 0)
    total = jnp.where(wrapped, jnp.int32(2**31 - 1), total)
    # Emitting rows have distinct starts (a wrapped, negative one must not
    # index from the end: it is dropped with the rows that emit nothing).
    head = jnp.where((m > 0) & (starts >= 0), starts, out_capacity)
    mark = jnp.zeros(out_capacity, jnp.int32).at[head].set(
        lax.iota(jnp.int32, n_rows) + 1, mode="drop")
    j = lax.iota(jnp.int32, out_capacity)
    owner = jnp.maximum(lax.cummax(mark) - 1, 0)
    offset = j - lax.cummax(jnp.where(mark > 0, j, 0))
    return owner, offset, total


def merge_ranks(lwords, rwords):
    """(lo, hi) for every row of a sorted left key column: how many rows of
    the sorted right key column hold a smaller key, and how many a key not
    larger — np.searchsorted(rkeys, lkeys, "left") and (…, "right") — from
    ONE merge of the two columns and a constant number of passes, where a
    binary search is log2(n) gathers of the whole query column, each
    waiting for the last. A side is a list of key words, major first: one
    word, or the (key, lo) pair of a two-word int64 key.

    Both columns are sorted over their whole length (padding included).
    They are concatenated and sorted by key, stably: on equal keys the
    left rows, which come first, stay first and keep their order.
    In merged order the running count of right rows is lo at a left row;
    hi is that count where the key's run ends, carried back over the run
    by a reverse running min. The left rows' position says where each
    reading belongs."""
    lcap = lwords[0].shape[0]
    n = lcap + rwords[0].shape[0]
    words = [jnp.concatenate(pair) for pair in zip(lwords, rwords)]
    *words, src = lax.sort((*words, lax.iota(jnp.int32, n)),
                           num_keys=len(words), is_stable=True)
    below = jnp.cumsum((src >= lcap).astype(jnp.int32))
    run_end = words[0][1:] != words[0][:-1]
    for w in words[1:]:
        run_end = run_end | (w[1:] != w[:-1])
    run_end = jnp.concatenate([run_end, jnp.ones(1, bool)])
    not_above = lax.cummin(
        jnp.where(run_end, below, jnp.int32(2**31 - 1)), reverse=True)
    # a right row's position is lcap or more: out of range, dropped
    zeros = jnp.zeros(lcap, jnp.int32)
    lo = zeros.at[src].set(below, mode="drop")
    hi = zeros.at[src].set(not_above, mode="drop")
    return lo, hi


@stage("merge_join")
def merge_join_expand(
    left: Cols, left_count: jax.Array,
    right: Cols, right_count: jax.Array,
    key_name: str,
    out_capacity: int,
    outer: bool = False,
    fill_value: float = 0,
    left_sorted: bool = False,   # caller guarantees valid-prefix + sorted
    right_sorted: bool = False,
    lo_name: str = None,         # low word of a two-column int64 key
) -> Tuple[Cols, jax.Array, jax.Array]:
    """General sort-merge join with duplicate keys on BOTH sides.

    Reference semantics (pair_rdd.rs:104-121 via cogroup): inner join emits
    the full dup x dup product per key; left outer emits every valid left
    row, with fill_value in right columns when unmatched. Static shapes:
    each left row's match range in the sorted right block comes from one
    merge of the two key columns (merge_ranks), and output rows are
    assigned by ragged expansion — per-left-row match counts -> exclusive
    prefix sums -> each output slot takes the last left row that started
    at or before it (ragged_expand) — so the product materializes into a fixed
    out_capacity with an overflow flag (the exchange capacity-factor
    pattern; driver retries with a larger capacity). Output rows are
    key-sorted (left sort order), deterministic across capacities.

    Returns (cols, count, total) where count = min(total, out_capacity) and
    total is the exact full product size — the driver uses it to size the
    ONE retry exactly instead of growing geometrically (a dup x dup product
    can exceed any constant growth factor). Right columns appear as
    "r_<name>".
    """
    lcap = left[key_name].shape[0]
    rcap = right[key_name].shape[0]
    if not left_sorted:
        left = sort_by_column(left, left_count, key_name, lo_name=lo_name)
    if not right_sorted:
        right = sort_by_column(right, right_count, key_name,
                               lo_name=lo_name)
    lmask = valid_mask(lcap, left_count)
    rmask = valid_mask(rcap, right_count)
    lkeys = left[key_name]
    # Both sides padded with the largest key, so each whole column is sorted.
    names = [key_name] if lo_name is None else [key_name, lo_name]
    lo, hi = merge_ranks(
        [jnp.where(lmask, left[n], _orderable_max(left[n])) for n in names],
        [jnp.where(rmask, right[n], _orderable_max(right[n])) for n in names])
    # Per-left-row match range in the sorted right block. The min() guards
    # clip sentinel-padded rows out when a valid key equals the sentinel.
    lo = jnp.minimum(lo, right_count)
    hi = jnp.minimum(hi, right_count)
    n_match = hi - lo
    if outer:
        m = jnp.where(lmask, jnp.maximum(n_match, 1), 0)
    else:
        m = jnp.where(lmask, n_match, 0)
    # Slot ownership via ragged_expand; total saturates to INT32_MAX when
    # a dup x dup product over 2^31 rows/shard would wrap (cannot
    # materialize anyway — 25+ GB of rows — but must fail loudly in the
    # driver, not return a truncated block).
    li, off, total = ragged_expand(m, out_capacity)
    ri = jnp.clip(jnp.take(lo, li) + off, 0, rcap - 1)
    row_matched = jnp.take(n_match > 0, li)

    key_set = {key_name} if lo_name is None else {key_name, lo_name}
    out: Cols = {key_name: jnp.take(lkeys, li)}
    if lo_name is not None:
        out[lo_name] = jnp.take(left[lo_name], li)
    for name, col in left.items():
        if name not in key_set:
            out[name] = jnp.take(col, li, axis=0)
    for name, col in right.items():
        if name in key_set:
            continue
        taken = jnp.take(col, ri, axis=0)
        if outer:
            fill = jnp.asarray(fill_value, dtype=col.dtype)
            mm = row_matched.reshape(row_matched.shape
                                     + (1,) * (taken.ndim - 1))
            taken = jnp.where(mm, taken, fill)
        out[f"r_{name}"] = taken
    # Valid output slots are the prefix [0, total) — already compact.
    count = jnp.minimum(total, out_capacity)
    return out, count, total


# ---------------------------------------------------------------------------
# misc per-shard reductions
# ---------------------------------------------------------------------------


@stage("named_reduce")
def masked_reduce(col: jax.Array, count: jax.Array, op: str) -> jax.Array:
    mask = valid_mask(col.shape[0], count)
    m = mask.reshape(mask.shape + (1,) * (col.ndim - 1))
    if op == "add":
        return jnp.sum(jnp.where(m, col, 0), axis=0)
    if op == "min":
        return jnp.min(jnp.where(m, col, _orderable_max(col)), axis=0)
    if op == "max":
        if jnp.issubdtype(col.dtype, jnp.floating):
            lo = jnp.array(-jnp.inf, col.dtype)
        else:
            lo = jnp.array(jnp.iinfo(col.dtype).min, col.dtype)
        return jnp.max(jnp.where(m, col, lo), axis=0)
    raise ValueError(f"unknown reduction {op}")


# ---------------------------------------------------------------------------
# GF(256) decode kernel (coded shuffle, shuffle/coding.py)
# ---------------------------------------------------------------------------


def gf256_accumulate(blocks, coeffs) -> jax.Array:
    """XOR-accumulate GF(256)-scaled byte rows: out = XOR_i c_i * B_i.

    The vectorized decode step of the coded shuffle (shuffle/coding.py):
    `blocks` is uint8[n, L] length-framed byte columns (survivor buckets
    zero-padded to the frame width), `coeffs` is uint8[n] GF(256)
    coefficients — all ones for the XOR scheme, Cauchy-matrix entries
    for rs(k, m). Multiplication is two log-table gathers plus an exp
    gather with the zero operands masked (log(0) is undefined; a zero
    factor makes the product zero), so the whole decode is gather/where/
    xor work the VPU streams. Must stay bit-identical to the numpy twin
    coding._accumulate_np — test_dense.py asserts host-vs-device parity.
    """
    from vega_tpu.shuffle.coding import GF_EXP, GF_LOG

    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    coeffs = jnp.asarray(coeffs, dtype=jnp.uint8)
    exp_t = jnp.asarray(GF_EXP, dtype=jnp.uint8)
    log_t = jnp.asarray(GF_LOG, dtype=jnp.int32)
    logs = (jnp.take(log_t, blocks.astype(jnp.int32))
            + jnp.take(log_t, coeffs.astype(jnp.int32))[:, None])
    prod = jnp.take(exp_t, logs)
    prod = jnp.where((blocks == 0) | (coeffs == 0)[:, None],
                     jnp.uint8(0), prod)
    out = jnp.zeros(blocks.shape[1], dtype=jnp.uint8)
    # Group sizes are small (k ≤ 128, typically 4): a static unrolled
    # XOR chain beats a lax.reduce round trip on every jax version.
    for i in range(blocks.shape[0]):
        out = lax.bitwise_xor(out, prod[i])
    return out
