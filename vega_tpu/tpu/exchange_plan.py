"""Collective-aware device exchange planner: cost-modeled reshard programs.

Every DenseRDD exchange used to be a one-shot program whose implementation
was picked by name (Configuration.dense_exchange) or by the frame layer's
ad-hoc size heuristic. This module is the ONE cost model both now share
(the PR 10 lesson: hand-rolled copies of a predicate drift apart): given
the launch-time facts of an exchange — mesh size, static per-shard
capacity, slot/out capacities, row bytes — it estimates the per-shard
transient-HBM high-water mark of each collective program and plans the
exchange as the cheapest program whose estimate fits the
Configuration.dense_hbm_budget:

  all_to_all  ONE fused lax.all_to_all; the [n_shards, slot] send/recv
              buffers per column grow linearly with mesh size — fastest
              (one collective round) but the HBM hazard on big meshes.
  staged      rows move in K sub-rounds of `group` peers each
              (ring.staged_exchange): per round, `group` shifted
              ppermutes share one stacked [group, slot] send/recv buffer
              per column and ONE bulk append — K chosen as the smallest
              round count whose estimated peak fits the budget.
  ring        the staged plan's group=1 extreme: a single bounded
              [slot] buffer per column, n-1 sequential rounds — minimum
              possible peak, chosen when no larger group fits.

This is the decomposition argument of "Memory-efficient array
redistribution through portable collective communication"
(arXiv:2112.01075) applied to keyed-data shuffles: an arbitrary reshard
becomes a *sequence* of portable collective blocks sized to bound the
high-water mark, rather than one monolithic collective sized by the
data. DrJAX (arXiv:2403.07128) supplies the sharded-map multi-round fold
idiom the staged program reuses.

The model is an ESTIMATE (XLA scheduling can overlap or rematerialize
buffers); it is deliberately conservative and only ever used to choose
between programs that are all correct — a wrong estimate costs
performance, never results. Correctness stays where it always was: the
(cols, count, overflow) contract, the n_shards==1 passthrough, and the
overflow -> grown-capacity retry loop (dense_rdd._run_exchange), all of
which every planned program keeps (machine-checked by vegalint VG014).

Consumers:
  dense_rdd._ExchangeRDD._resolve_exchange  dense_exchange=auto resolution
  tpu/stream.planned_chunk_rows             chunk sizing replaces the
                                            fixed 6x footprint constant
  frame/planner._pick_exchange              the frame layer's per-exchange
                                            policy (same model, no copy)
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Dict, Optional

from vega_tpu.errors import VegaError

log = logging.getLogger("vega_tpu")

MODES = ("auto", "all_to_all", "ring", "staged")
PROGRAMS = ("all_to_all", "ring", "staged")


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """One exchange launch's planned collective program.

    est_peak_bytes is the modeled per-shard transient high-water mark:
    operand block + bucket-grouped copy + the program's collective
    buffers + the compacted output, all at static capacities (padding
    rows occupy HBM like any others — Block.nbytes has the same
    convention). rounds counts collective rounds (1 for the one-shot
    all_to_all, n-1 for ring, ceil((n-1)/group) for staged)."""

    program: str            # "all_to_all" | "ring" | "staged"
    n_shards: int
    rounds: int
    group: int              # peers per round (staged; 1 ring, n-1 one-shot)
    est_peak_bytes: int     # per-shard transient HBM high-water estimate
    est_bytes_moved: int    # per-shard wire bytes (all programs move the
                            # same rows; rounds differ, not volume)
    budget_bytes: int
    fits: bool              # est_peak_bytes <= budget_bytes

    def cache_token(self) -> tuple:
        """Program-cache identity of the resolved choice. The budget is
        config (NOT part of dense_rdd's program-cache keys), so the
        RESOLVED program must be — a mid-process budget flip then mints a
        fresh program instead of silently reusing the old plan."""
        return (self.program, self.group)


def row_bytes_of(dtypes_and_trailing) -> int:
    """Per-row bytes of a column schema: sum of itemsize * trailing-dim
    product over (dtype, trailing_shape) pairs."""
    total = 0
    for dt, trailing in dtypes_and_trailing:
        n = 1
        for d in trailing:
            n *= int(d)
        total += dt.itemsize * n
    return max(total, 1)


def block_row_bytes(blk) -> int:
    """Per-row bytes of a Block's columns (trailing dims included)."""
    return row_bytes_of(
        (c.dtype, c.shape[1:]) for c in blk.cols.values())


def transient_rows(program: str, n_shards: int, slot_capacity: int,
                   group: int = 1) -> int:
    """Collective-buffer rows live at once per column, per shard. The
    one-shot all_to_all holds its send buffer plus the received mirror
    (2 x [n, slot]); the staged/ring append additionally stacks the
    round's received slots into one contiguous buffer for the bulk
    scatter while the mirrors are still live (ring.append_round), so
    those programs carry a third copy of the round's slots — modeling
    2x there let a fits=True staged plan bust the budget it was chosen
    to respect."""
    if program == "all_to_all":
        return 2 * n_shards * slot_capacity
    if program == "ring":
        return 3 * slot_capacity
    return 3 * group * slot_capacity  # staged


def estimate_peak_bytes(program: str, n_shards: int, capacity: int,
                        slot_capacity: int, out_capacity: int,
                        row_bytes: int, group: int = 1,
                        blocks=None) -> int:
    """Per-shard transient HBM high-water estimate of one exchange
    program: operand + bucket-grouped copy + collective buffers +
    compacted output. The n_shards==1 passthrough never builds
    collective buffers or a grouped copy.

    `blocks` — [(capacity, row_bytes), ...] — models a launch that
    exchanges SEVERAL operand blocks (a dup x dup join moves both
    sides in one program): every block's operand and compacted output
    are live together across the launch, but the sides exchange
    SEQUENTIALLY, so only the costliest side's bucket-grouped copy and
    collective buffers contribute to the high-water mark. For a single
    block this reduces exactly to the one-block formula."""
    if blocks is None:
        blocks = [(capacity, row_bytes)]
    if n_shards == 1:
        return sum((cap + out_capacity) * rb for cap, rb in blocks)
    trans = transient_rows(program, n_shards, slot_capacity, group)
    resident = sum((cap + out_capacity) * rb for cap, rb in blocks)
    exchanging = max(cap * rb + trans * rb for cap, rb in blocks)
    return resident + exchanging


def _plan(program: str, n_shards: int, capacity: int, slot_capacity: int,
          out_capacity: int, row_bytes: int, budget_bytes: int,
          group: int, rounds: int, blocks=None) -> ExchangePlan:
    peak = estimate_peak_bytes(program, n_shards, capacity, slot_capacity,
                               out_capacity, row_bytes, group,
                               blocks=blocks)
    # Worst case every valid row leaves its shard: capacity rows out and
    # (symmetrically) up to out_capacity rows in, summed over every
    # block the launch moves.
    moved = sum(
        (min(cap, (n_shards - 1) * slot_capacity) + out_capacity) * rb
        for cap, rb in (blocks or [(capacity, row_bytes)])
    ) if n_shards > 1 else 0
    return ExchangePlan(
        program=program, n_shards=n_shards, rounds=rounds, group=group,
        est_peak_bytes=peak, est_bytes_moved=moved,
        budget_bytes=budget_bytes, fits=peak <= budget_bytes,
    )


def plan_exchange(n_shards: int, capacity: int, slot_capacity: int,
                  out_capacity: int, row_bytes: int, budget_bytes: int,
                  mode: str = "auto", blocks=None) -> ExchangePlan:
    """Plan one exchange launch.

    mode "all_to_all"/"ring"/"staged" force that program (staged still
    picks the largest group — fewest rounds — that fits the budget);
    "auto" picks the fewest-rounds program whose estimated peak fits:
    the one-shot all_to_all when it does, otherwise the staged program
    with the smallest K (largest peer group) that fits, otherwise ring
    (the minimum-possible-peak extreme — chosen even when its estimate
    still exceeds the budget, because some program must run and ring's
    single bounded buffer is the best any exchange can do).

    blocks — optional [(capacity, row_bytes), ...] — models a launch
    that moves several operand blocks (a join's two non-elided sides);
    see estimate_peak_bytes. capacity/row_bytes then only seed the
    single-block fallback and may be the maxima."""
    if mode not in MODES:
        raise VegaError(
            f"dense_exchange must be one of "
            f"{', '.join(repr(m) for m in MODES)}; got {mode!r}")
    if n_shards <= 1:
        # Passthrough territory: no collective, one "round", trivially
        # the cheapest shape of the one-shot program.
        return _plan("all_to_all", max(n_shards, 1), capacity,
                     slot_capacity, out_capacity, row_bytes, budget_bytes,
                     group=0, rounds=0, blocks=blocks)

    def one_shot():
        return _plan("all_to_all", n_shards, capacity, slot_capacity,
                     out_capacity, row_bytes, budget_bytes,
                     group=n_shards - 1, rounds=1, blocks=blocks)

    def ring():
        return _plan("ring", n_shards, capacity, slot_capacity,
                     out_capacity, row_bytes, budget_bytes,
                     group=1, rounds=n_shards - 1, blocks=blocks)

    def staged(group: int):
        rounds = -(-(n_shards - 1) // group)
        return _plan("staged", n_shards, capacity, slot_capacity,
                     out_capacity, row_bytes, budget_bytes,
                     group=group, rounds=rounds, blocks=blocks)

    if mode == "all_to_all":
        return one_shot()
    if mode == "ring":
        return ring()
    if mode == "staged":
        for g in range(n_shards - 1, 1, -1):
            p = staged(g)
            if p.fits:
                return p
        return staged(1)
    # auto. The staged search starts at group = n-1 (fewest rounds); with
    # the 3x slot coefficient its estimate can exceed the one-shot's
    # (3*(n-1) vs 2*n slots for n > 3), in which case it simply never
    # fits a budget the one-shot already busted and the search steps
    # down to smaller groups.
    p = one_shot()
    if p.fits:
        return p
    for g in range(n_shards - 1, 1, -1):
        s = staged(g)
        if s.fits:
            return s
    r = ring()
    if not r.fits:
        log.info(
            "exchange planner: even the ring program's estimated peak "
            "(%d B) exceeds dense_hbm_budget (%d B) — running it anyway "
            "(minimum possible footprint); shrink the block or stream",
            r.est_peak_bytes, r.budget_bytes)
    return r


def exchange_callable(plan: ExchangePlan):
    """The exchange implementation for a plan, with the staged group
    bound — a drop-in for the (cols, count, bucket, n_shards, slot,
    out_capacity, pregrouped=) call shape every exchange site uses."""
    if plan.program == "ring":
        from vega_tpu.tpu.ring import ring_exchange

        return ring_exchange
    if plan.program == "staged":
        import functools

        from vega_tpu.tpu.ring import staged_exchange

        return functools.partial(staged_exchange, group=plan.group)
    from vega_tpu.tpu import kernels

    return kernels.bucket_exchange


# ---------------------------------------------------------------------------
# observability: module counters tests and benchmarks can read
# ---------------------------------------------------------------------------

_counters_lock = threading.Lock()
_PLAN_COUNTS: Dict[str, int] = {}
_LAST_PLAN: Optional[ExchangePlan] = None


def record_plan(plan: ExchangePlan) -> None:
    global _LAST_PLAN
    with _counters_lock:
        _PLAN_COUNTS[plan.program] = _PLAN_COUNTS.get(plan.program, 0) + 1
        _LAST_PLAN = plan


def plan_counters() -> Dict[str, int]:
    """Launches planned per program since process start (or the last
    reset): the DenseRDD-level counter tests key acceptance on."""
    with _counters_lock:
        return dict(_PLAN_COUNTS)


def last_plan() -> Optional[ExchangePlan]:
    with _counters_lock:
        return _LAST_PLAN


def reset_plan_counters() -> None:
    global _LAST_PLAN
    with _counters_lock:
        _PLAN_COUNTS.clear()
        _LAST_PLAN = None


# ---------------------------------------------------------------------------
# derived sizing: per-shard budget shares, streamed chunking, and the
# frame layer's prediction
# ---------------------------------------------------------------------------


def memory_sharing_factor(n_shards: int) -> int:
    """How many shards share ONE memory space — the divisor between the
    per-chip dense_hbm_budget and the budget each shard's exchange may
    actually plan against.

    Real accelerator devices (TPU/GPU) own their HBM: factor 1, every
    shard plans against the full per-chip budget. CPU meshes are VIRTUAL
    devices of one host (the 8-device proxy mesh, the streamed-1B
    single-chip shape): all n shards' transients land in the same RAM,
    so each shard gets budget/n — without this, n per-shard-fitting
    one-shot exchanges aggregate to n x budget on one chip (the bound
    the planner exists to hold). Multi-process CPU test meshes divide by
    the full n rather than the per-process count — over-conservative,
    and only test topologies run there. Backend probing happens here at
    materialize/planning time, never at import (CLAUDE.md quirk)."""
    import jax

    if n_shards <= 1:
        return 1
    return n_shards if jax.default_backend() == "cpu" else 1


def per_shard_budget(n_shards: int, budget_bytes: int) -> int:
    """The budget one shard's exchange plans against: the per-chip
    budget divided across the shards sharing its memory space."""
    return max(budget_bytes // memory_sharing_factor(n_shards), 1)


def _heuristic_caps(total_rows: int, n_shards: int):
    """The capacities an exchange over `total_rows` would run at: the
    per-shard capacity of an even split, with slot/out from the REAL
    launch-time sizing (dense_rdd._exchange_capacities) fed synthetic
    even per-shard counts — one source of truth, so a tweak to the
    launch heuristics (skew allowance, rounding) cannot silently
    desynchronize pre-materialization planning from launch planning.
    The even-split cold-path sizing is a superset of the
    histogram-sized warm path, so the estimate errs conservative."""
    import numpy as np

    from vega_tpu.tpu.block import _round_capacity
    from vega_tpu.tpu.dense_rdd import _exchange_capacities

    n = max(n_shards, 1)
    per = max(-(-total_rows // n), 1)
    slot, out = _exchange_capacities(
        np.full(n, per, dtype=np.int64), n, attempt=0)
    return _round_capacity(per), slot, out


def predict_for_rows(total_rows: int, row_bytes: int, n_shards: int,
                     budget_bytes: int) -> ExchangePlan:
    """Plan an exchange from a pre-materialization row estimate (the
    frame planner's view: metadata only, nothing materialized). Plans
    against the per-shard budget share: on real accelerators (factor 1)
    that IS the launch-time resolution's budget, so the prediction and
    the eventual plan agree exactly; on shared-memory CPU proxy meshes
    the share is stricter than the launch's per-chip budget, so the
    prediction errs toward opting exchanges into planner resolution —
    a conservative note, never a forced program."""
    cap, slot, out = _heuristic_caps(total_rows, n_shards)
    return plan_exchange(n_shards, cap, slot, out, row_bytes,
                         per_shard_budget(n_shards, budget_bytes),
                         mode="auto")


def planned_stream_rows(n_rows: int, bytes_per_row: int,
                        budget_bytes: int,
                        n_shards: int) -> Optional[int]:
    """Planner-derived chunk sizing for streamed sources: the largest
    chunk whose AGGREGATE planned exchange peak (summed over shards —
    the streamed 1B path runs all shards of one chip, so per-shard
    transients share one HBM) fits the budget. None when the whole
    source fits resident. Replaces stream.py's fixed 6x footprint: a
    bounded (staged/ring) plan's transients are a small slice of the
    block, so chunks grow toward the operand+copy+output floor and the
    multi-pass fold pays fewer passes.

    Planning runs against the PER-SHARD budget share (per_shard_budget
    divides the per-chip budget across memory-sharing shards), and the
    fit check multiplies the planned peak back by the sharing factor —
    so the aggregate bound is share x factor <= budget by construction.
    On real accelerators the factor is 1 and the share IS the budget
    the launch-time resolution (_resolve_exchange) plans against, so
    sizing and launch agree exactly. On the shared-memory CPU proxy the
    launch still plans per shard against the per-chip budget (the
    knob's contract, and what the program-choice tests calibrate) and
    may pick a roomier program than the share-planned one — there the
    chunk bound is sized for the bounded-program footprint, the honest
    target on the one host whose RAM all shards share; the launch's
    roomier choice trades that slack for fewer rounds, exactly the
    planner's job. The fits-predicate is monotone in rows
    (within one program peaks grow with capacity; at a program switch
    the planner only ever steps DOWN to a cheaper-peak program), which
    the binary search requires."""
    factor = memory_sharing_factor(n_shards)
    share = per_shard_budget(n_shards, budget_bytes)

    def fits(rows: int) -> bool:
        cap, slot, out = _heuristic_caps(rows, n_shards)
        plan = plan_exchange(n_shards, cap, slot, out, bytes_per_row,
                             share, mode="auto")
        return factor * plan.est_peak_bytes <= budget_bytes

    if fits(n_rows):
        return None
    lo, hi = 1, n_rows
    while lo < hi:  # max rows whose planned aggregate peak fits
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    return max(lo, 1)
