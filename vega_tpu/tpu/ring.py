"""Ring + staged exchanges: peak-memory-bounded alternatives to all_to_all.

bucket_exchange (kernels.py) materializes an [n_shards, slot_capacity] send
buffer per column — peak memory grows linearly with mesh size, which is the
HBM hazard for large blocks on big meshes. The bounded exchanges here
instead move rows in ROUNDS of `group` peers each: per round, each shard
selects the rows destined for peers (i+s) mod n for the round's shifts s,
ppermutes them around the ring sharing one stacked [group, slot_capacity]
send/recv buffer per column, and bulk-appends what arrives in ONE scatter
— peak extra memory is 3*group slots per column regardless of mesh size
(send slots + received mirrors + the append's stacked contiguous copy —
the coefficient exchange_plan.transient_rows charges), at
ceil((n-1)/group) sequential rounds.

group interpolates the whole trade: group=1 is the classic ring (a single
bounded buffer, n-1 rounds — ring_exchange delegates here); group=n-1 is
one round whose buffers match the all_to_all footprint. The collective-
aware planner (tpu/exchange_plan.py) picks the group per launch so the
estimated peak fits Configuration.dense_hbm_budget — the decomposition of
"Memory-efficient array redistribution through portable collective
communication" (arXiv:2112.01075): arbitrary reshards as *sequences* of
bounded-footprint collective blocks. Lane-adjacent shifts ride neighbor
ICI links on a physical ring/torus (the ring-attention pipelining
pattern applied to keyed-data shuffles).

Select per shuffle with the exchange= keyword
(DenseRDD.reduce_by_key/group_by_key/join/sort_by_key) or globally via
Configuration.dense_exchange / VEGA_TPU_DENSE_EXCHANGE: "auto" (default)
routes through the planner, "ring"/"staged"/"all_to_all" force a program.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from vega_tpu.tpu import kernels
from vega_tpu.tpu.mesh import SHARD_AXIS
from vega_tpu.tpu.spans import stage

Cols = Dict[str, jax.Array]


def ring_exchange(
    cols: Cols,
    count: jax.Array,
    bucket: jax.Array,
    n_shards: int,
    slot_capacity: int,
    out_capacity: int,
    pregrouped: bool = False,
) -> Tuple[Cols, jax.Array, jax.Array]:
    """Drop-in replacement for kernels.bucket_exchange (same contract:
    returns (cols, new_count, overflow_flag)): the group=1 extreme of the
    staged exchange — one bounded [slot_capacity] buffer per column,
    n-1 sequential ppermute rounds."""
    if n_shards == 1:
        return kernels.passthrough_exchange(cols, count, bucket.shape[0],
                                            out_capacity)
    return staged_exchange(cols, count, bucket, n_shards, slot_capacity,
                           out_capacity, pregrouped=pregrouped, group=1)


def staged_exchange(
    cols: Cols,
    count: jax.Array,
    bucket: jax.Array,
    n_shards: int,
    slot_capacity: int,
    out_capacity: int,
    pregrouped: bool = False,
    group: int = 1,
) -> Tuple[Cols, jax.Array, jax.Array]:
    """Blocked/staged exchange: rows move in ceil((n-1)/group) rounds of
    `group` shifted ppermutes each. Same contract as
    kernels.bucket_exchange — returns (cols, new_count, overflow_flag);
    pregrouped means rows are already contiguous per bucket, so grouping
    collapses to a bincount.

    Per round the live transient per column is one stacked
    [group, slot_capacity] send buffer plus its received mirror, and the
    round's arrivals land in ONE bulk scatter into the output — fewer
    O(out_capacity) append passes than the classic ring (rounds, not
    n-1) while the peak stays bounded at 2*group slots. The planner
    (tpu/exchange_plan.py) chooses `group` so that bound fits the HBM
    budget."""
    capacity = bucket.shape[0]
    if n_shards == 1:
        return kernels.passthrough_exchange(cols, count, capacity,
                                            out_capacity)
    group = max(1, min(int(group), n_shards - 1))
    with stage("exchange_group"):
        mask = kernels.valid_mask(capacity, count)
        bucket = jnp.where(mask, bucket, n_shards)

        if pregrouped:
            counts_to, starts = kernels.pregrouped_group(bucket, n_shards)
            sorted_cols = cols
        else:
            # prefer_low_memory: the counting sort's O(capacity * n_shards)
            # intermediates would defeat exactly the peak-memory bound this
            # exchange exists to provide.
            sorted_cols, counts_to, starts = kernels._group_by_bucket(
                cols, bucket, n_shards, prefer_low_memory=True)
        overflow = jnp.any(counts_to > slot_capacity)

    my_id = lax.axis_index(SHARD_AXIS)

    out_cols: Cols = {
        name: jnp.zeros((out_capacity,) + col.shape[1:], col.dtype)
        for name, col in cols.items()
    }
    write_pos = jnp.zeros((), jnp.int32)

    @stage("exchange_send")
    def take_slot(target):
        """[slot_capacity] rows destined for `target` + their count: the
        one-destination form of kernels.send_slots."""
        start = lax.dynamic_slice_in_dim(starts, target, 1)
        n_to = lax.dynamic_slice_in_dim(counts_to, target, 1)
        slot = {name: kernels.send_slots(col, start, n_to, slot_capacity)[0]
                for name, col in sorted_cols.items()}
        return slot, jnp.minimum(n_to[0], slot_capacity).astype(jnp.int32)

    @stage("exchange_compact")
    def append_round(out_cols, write_pos, slots, rows_list):
        """Bulk-append one round's received slots: one scatter per column
        over the stacked [g, slot_capacity] buffer."""
        g = len(slots)
        rows_vec = jnp.stack(rows_list)                 # [g]
        offs = jnp.cumsum(rows_vec) - rows_vec          # exclusive prefix
        j = jnp.arange(slot_capacity)[None, :]
        idx = write_pos + offs[:, None] + j             # [g, slot]
        in_range = j < rows_vec[:, None]
        idx = jnp.where(in_range, idx, out_capacity)    # OOB rows dropped
        flat_idx = idx.reshape(-1)
        new = {}
        for name, out in out_cols.items():
            stacked = jnp.stack([s[name] for s in slots])  # [g, slot, ...]
            flat = stacked.reshape((g * slot_capacity,)
                                   + stacked.shape[2:])
            new[name] = out.at[flat_idx].set(flat, mode="drop")
        return new, write_pos + jnp.sum(rows_vec)

    # Round 0: my own bucket stays local.
    slot, n_rows = take_slot(my_id)
    out_cols, write_pos = append_round(out_cols, write_pos, [slot],
                                       [n_rows])

    # Rounds of `group` shifts: send to peer (i+s) mod n via an s-hop
    # shifted ppermute. The loop is unrolled (perms must be static); each
    # round's live buffers are the stacked [group, slot] send slots and
    # their received mirrors.
    for r0 in range(1, n_shards, group):
        recv_slots = []
        recv_rows = []
        for s in range(r0, min(r0 + group, n_shards)):
            perm = [(i, (i + s) % n_shards) for i in range(n_shards)]
            target = (my_id + s) % n_shards
            slot, n_rows = take_slot(target)
            with stage("exchange_wire"):
                recv_slots.append({
                    name: lax.ppermute(c, SHARD_AXIS, perm)
                    for name, c in slot.items()
                })
                recv_rows.append(lax.ppermute(n_rows, SHARD_AXIS, perm))
        out_cols, write_pos = append_round(out_cols, write_pos,
                                           recv_slots, recv_rows)

    total_in = write_pos
    # Rows destined for me but truncated by slot_capacity at any sender are
    # invisible here; senders flag that via `overflow` (any counts_to > slot).
    overflow = overflow | (total_in > out_capacity)
    return out_cols, total_in.astype(jnp.int32), overflow
