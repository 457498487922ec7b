"""Pallas TPU kernels for the dense tier's hot scalar ops.

The exchange pipeline's non-sort cost is hashing + bucketing every key
(tpu/kernels.py hash32). XLA fuses these elementwise ops well, but routing
them through Pallas keeps the whole hash+bucket step in one VMEM-resident
kernel (no intermediate HBM round trips between the four mixer stages) and
establishes the kernel plumbing richer kernels can extend.

Kernels run compiled on TPU and in interpreter mode elsewhere (tests run
interpret=True on CPU). All shapes are padded to the (8, 128) f32/i32 tile
internally; callers see flat arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES


def _hash_bucket_kernel(keys_ref, out_ref, *, n_buckets: int):
    """lowbias32 finalizer + modulo bucketing, one VMEM block at a time."""
    x = keys_ref[:].astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    out_ref[:] = (x % jnp.uint32(n_buckets)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def hash_bucket_pallas(keys: jax.Array, n_buckets: int,
                       interpret: bool = False) -> jax.Array:
    """bucket = lowbias32(key) % n_buckets via one Pallas kernel.

    Bit-identical to kernels.hash32(...) % n_buckets for int32 keys (the
    device-tier bucketing contract)."""
    n = keys.shape[0]
    padded = -(-n // _TILE) * _TILE
    grid = padded // _TILE
    keys2d = jnp.pad(keys, (0, padded - n)).reshape(-1, _LANES)

    out = pl.pallas_call(
        functools.partial(_hash_bucket_kernel, n_buckets=n_buckets),
        out_shape=jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
        grid=(grid,),
        # index_map yields BLOCK indices (block i covers rows
        # [i*_SUBLANES, (i+1)*_SUBLANES) of the 2D view).
        in_specs=[pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(keys2d)
    return out.reshape(-1)[:n]


def hash_bucket(keys: jax.Array, n_buckets: int) -> jax.Array:
    """Platform-dispatched bucketing: Pallas on TPU, plain XLA elsewhere
    (pallas interpret mode is for tests, not production CPU)."""
    from vega_tpu.tpu import kernels

    if keys.dtype == jnp.int32 and jax.default_backend() == "tpu":
        return hash_bucket_pallas(keys, n_buckets)
    return (kernels.hash32(keys) % jnp.uint32(n_buckets)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# counting-partition rank kernel
# ---------------------------------------------------------------------------
#
# The stable counting partition (kernels._group_by_bucket) needs, per row,
# pos = starts[bucket] + (# earlier rows with the same bucket). The XLA
# formulation materializes a [capacity, n_buckets+1] one-hot plus its
# column cumsum in HBM — O(capacity * k) reads+writes. This kernel streams
# the bucket column ONCE: per (8, 128) VMEM tile it computes in-tile
# exclusive ranks with 2D cumsums (statically unrolled over the small
# bucket range) and carries per-bucket running totals across the
# sequential grid in a VMEM scratch — O(capacity) HBM traffic total.


def _partition_pos_kernel(starts_ref, bucket_ref, pos_ref, carry_ref,
                          *, n_bins: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for bb in range(n_bins):  # SMEM takes scalar stores only
            carry_ref[0, bb] = 0

    b = bucket_ref[:]  # (8, 128) int32, values in [0, n_bins)
    pos = jnp.zeros_like(b)
    # Mosaic has no cumsum primitive: exclusive prefix sums become
    # triangular matmuls (exact in f32 — tile counts are < 2^24).
    lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    upper = (lane < lane_t).astype(jnp.float32)  # strict: exclusive
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _SUBLANES), 0)
    sub_t = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _SUBLANES), 1)
    lower = (sub_t < sub).astype(jnp.float32)
    for bb in range(n_bins):  # static unroll: n_bins small (mesh size + 1)
        m = (b == bb).astype(jnp.float32)
        # exclusive prefix count in row-major tile order: within-sublane
        # prefix + whole-earlier-sublane totals
        cs_l = jnp.dot(m, upper, preferred_element_type=jnp.float32)
        row_tot = jnp.sum(m, axis=1, keepdims=True)  # (8, 1)
        cs_s = jnp.dot(lower, row_tot,
                       preferred_element_type=jnp.float32)
        excl = (cs_l + cs_s).astype(jnp.int32)
        base = starts_ref[0, bb] + carry_ref[0, bb]
        sel = m.astype(jnp.int32)
        pos = pos + sel * (base + excl)
        carry_ref[0, bb] = carry_ref[0, bb] + \
            jnp.sum(m).astype(jnp.int32)
    pos_ref[:] = pos


@functools.partial(jax.jit, static_argnums=(1, 3))
def partition_pos_pallas(bucket: jax.Array, n_bins: int,
                         starts: jax.Array,
                         interpret: bool = False) -> jax.Array:
    """pos[i] = starts[bucket[i]] + |{j < i : bucket[j] == bucket[i]}|.

    bucket values must lie in [0, n_bins) (callers pass n_shards + 1 bins:
    real buckets plus the ghost). starts is int32[n_bins] (exclusive
    prefix of the per-bucket totals). Bit-identical to the XLA one-hot
    rank path in kernels._group_by_bucket."""
    n = bucket.shape[0]
    padded = -(-n // _TILE) * _TILE
    grid = padded // _TILE
    # padding rows use bucket n_bins-1 (the ghost bin): they come after
    # every real row, so real positions are unaffected; their pos values
    # are sliced off below.
    b2d = jnp.pad(bucket, (0, padded - n),
                  constant_values=n_bins - 1).reshape(-1, _LANES)
    starts_pad = -(-n_bins // _LANES) * _LANES
    starts2d = jnp.pad(starts.astype(jnp.int32),
                       (0, starts_pad - n_bins)).reshape(1, -1)

    out = pl.pallas_call(
        functools.partial(_partition_pos_kernel, n_bins=n_bins),
        out_shape=jax.ShapeDtypeStruct(b2d.shape, jnp.int32),
        grid=(grid,),
        in_specs=[
            # per-bucket scalars live in SMEM: the kernel reads/writes
            # them one element at a time (VMEM refuses scalar stores)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
        scratch_shapes=[pltpu.SMEM((1, starts_pad), jnp.int32)],
        interpret=interpret,
    )(starts2d, b2d)
    return out.reshape(-1)[:n]


def _digit_hist_kernel(d_ref, hist_ref, *, n_bins: int):
    """Accumulate per-bin counts across the sequential grid. hist lives
    in SMEM (scalar stores only)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for bb in range(n_bins):
            hist_ref[0, bb] = 0

    b = d_ref[:]
    for bb in range(n_bins):
        hist_ref[0, bb] += jnp.sum((b == bb).astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(1, 2))
def digit_hist_pallas(digits: jax.Array, n_bins: int,
                      interpret: bool = False) -> jax.Array:
    """Histogram of small-range int32 digits in one streaming pass
    (per-tile counts accumulated in SMEM) — no [n, n_bins] one-hot in
    HBM. Padding rows land in bin n_bins-1; the caller's use (exclusive
    prefix starts) never reads that bin's count downstream of real rows
    in lower bins."""
    n = digits.shape[0]
    padded = -(-n // _TILE) * _TILE
    grid = padded // _TILE
    d2d = jnp.pad(digits, (0, padded - n),
                  constant_values=n_bins - 1).reshape(-1, _LANES)
    pad_bins = -(-n_bins // _LANES) * _LANES

    out = pl.pallas_call(
        functools.partial(_digit_hist_kernel, n_bins=n_bins),
        out_shape=jax.ShapeDtypeStruct((1, pad_bins), jnp.int32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(d2d)
    hist = out.reshape(-1)[:n_bins]
    # un-count the padding rows from the top bin
    return hist.at[n_bins - 1].add(-(padded - n))


def _xla_onehot_pos(bucket: jax.Array, starts: jax.Array,
                    n_bins: int) -> jax.Array:
    """XLA rank path: [n, n_bins] one-hot + column cumsum (O(n * n_bins)
    HBM intermediates)."""
    one_hot = (bucket[:, None] ==
               jnp.arange(n_bins)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(one_hot, axis=0), bucket[:, None], axis=1)[:, 0] - 1
    return jnp.take(starts, bucket) + rank


def _xla_argsort_pos(bucket: jax.Array, starts: jax.Array,
                     n_bins: int) -> jax.Array:
    """XLA low-memory rank path: positions from a stable argsort
    (O(n log n) time, O(n) memory — no one-hot intermediates)."""
    del starts  # the sorted order already encodes starts+rank
    n = bucket.shape[0]
    order = jnp.argsort(bucket, stable=True)
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


def bucket_hist(bucket: jax.Array, n_bins: int) -> jax.Array:
    """Per-bucket counts (bincount replacement for small bucket ranges),
    platform-selected at lowering: the Pallas streaming histogram on TPU
    (jnp.bincount lowers to scatter-adds there), bincount elsewhere.
    Large ranges keep bincount everywhere — the kernel statically
    unrolls a per-bin step, same bound as the rank kernel's gate."""
    if n_bins > 65:
        return jnp.bincount(bucket, length=n_bins).astype(jnp.int32)
    return jax.lax.platform_dependent(
        bucket,
        tpu=lambda b: digit_hist_pallas(b, n_bins),
        default=lambda b: jnp.bincount(b, length=n_bins).astype(jnp.int32),
    )


def partition_pos(bucket: jax.Array, n_bins: int, starts: jax.Array,
                  prefer_low_memory: bool = False):
    """Partition ranks pos[i] = starts[bucket[i]] + earlier-equal count,
    platform-selected AT LOWERING TIME (lax.platform_dependent): tpu gets
    the Pallas kernel — so a program exported with platforms=["tpu"]
    carries the Mosaic kernel and the offline lowering tier validates the
    REAL composed TPU program — other platforms get the XLA one-hot path,
    or the argsort path under prefer_low_memory (on TPU the Pallas kernel
    already streams in O(n), so the flag only shapes the fallback).
    Returns None when the kernel can't apply (caller keeps its own path)."""
    if n_bins > 65 or bucket.dtype != jnp.int32:
        return None
    fallback = _xla_argsort_pos if prefer_low_memory else _xla_onehot_pos
    return jax.lax.platform_dependent(
        bucket, starts,
        tpu=lambda b, s: partition_pos_pallas(b, n_bins, s),
        default=lambda b, s: fallback(b, s, n_bins),
    )
