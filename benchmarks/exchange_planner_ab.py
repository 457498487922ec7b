"""Exchange planner A/B (PR 13): one-shot all_to_all vs cost-modeled plan.

Acceptance shape for the collective-aware exchange planner
(tpu/exchange_plan.py): an exchange whose one-shot all_to_all footprint
exceeds a deliberately small dense_hbm_budget must complete FULLY ON
DEVICE via a staged (K>1 round) plan — no host round-trip — with the
estimated peak <= budget and results bit-identical to the one-shot leg;
and the streamed path must size bigger chunks from the planner's
per-exchange estimate than the legacy 6x footprint rule.

Legs (interleaved per rep against host drift, medians of 3):
  one_shot  dense_exchange=all_to_all at the default budget
  planned   dense_exchange=auto at a budget set to ~80% of the one-shot
            leg's own peak estimate (self-scaling: whatever `rows` is,
            the one-shot footprint busts it and the planner must stage)

Bit-identicality is asserted on order-free results (a named int add —
commutative, so reduction order cannot show — and a unique-key sort):
duplicate-key ties keep exchange ARRIVAL order, which differs between
collective programs by design (documented since the ring exchange).

Runs wherever jax lands (CPU proxy mesh locally, the real mesh on a TPU
machine). One JSON line.
Usage: python benchmarks/exchange_planner_ab.py [rows]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TPU = os.environ.get("VEGA_EXCHANGE_PLANNER_AB_TPU") == "1"
if not _TPU:
    from _cpu_mesh import force_cpu_mesh  # noqa: E402

    force_cpu_mesh(8)


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 400_000

    import jax
    import numpy as np

    import vega_tpu as v
    from vega_tpu.env import Env
    from vega_tpu.tpu import exchange_plan
    from vega_tpu.tpu.dense_rdd import DenseRDD
    from vega_tpu.tpu.stream import StreamedDenseRDD, planned_chunk_rows

    result = {"bench": "exchange_planner_ab", "rows": rows,
              "backend": jax.default_backend()}

    rng = np.random.RandomState(0)
    keys = rng.randint(0, max(rows // 200, 7), size=rows).astype(np.int32)
    vals = rng.randint(0, 1 << 20, size=rows).astype(np.int32)
    skeys = rng.permutation(rows).astype(np.int32)

    ctx = v.Context("local")
    conf = Env.get().conf
    from vega_tpu.tpu import mesh as mesh_lib

    if mesh_lib.default_mesh().size == 1:
        # A 1-device mesh takes the n_shards==1 passthrough — there is
        # no exchange to plan. Emit the one JSON line and bail.
        result["note"] = "single-device mesh: no exchange to plan"
        result["accept"] = {"skipped_single_device": True}
        ctx.stop()
        print(json.dumps(result))
        return
    saved = (conf.dense_exchange, conf.dense_hbm_budget)
    try:
        def pipeline():
            red = (ctx.dense_from_numpy(keys, vals)
                   .reduce_by_key(op="add"))
            srt = ctx.dense_from_numpy(skeys, vals).sort_by_key()
            t0 = time.time()
            red_rows = red.collect()
            srt_rows = srt.collect()
            wall = time.time() - t0
            return red, srt, dict(red_rows), srt_rows, wall

        # Cold pass of the one-shot leg: compiles, and its own plan
        # estimate calibrates the constrained budget.
        conf.dense_exchange = "all_to_all"
        red_a, _, base_red, base_srt, _ = pipeline()
        one_shot_peak = red_a._exchange_plan.est_peak_bytes
        result["one_shot_est_peak_bytes"] = one_shot_peak
        budget = int(one_shot_peak * 0.8)
        result["constrained_budget_bytes"] = budget

        # Cold pass of the planned leg (compile; verify the plan shape).
        conf.dense_exchange = "auto"
        conf.dense_hbm_budget = budget
        exchange_plan.reset_plan_counters()
        red_b, srt_b, red_rows_b, srt_rows_b, _ = pipeline()
        counters = exchange_plan.plan_counters()
        plan = red_b._exchange_plan
        result["planned"] = {
            "program": plan.program, "group": plan.group,
            "rounds": plan.rounds, "est_peak_bytes": plan.est_peak_bytes,
            "counters": counters,
        }
        staged_on_device = (
            isinstance(red_b, DenseRDD) and isinstance(srt_b, DenseRDD)
            and plan.program == "staged" and plan.rounds > 1
            and srt_b._exchange_plan.program == "staged")
        est_le_budget = (plan.est_peak_bytes <= budget
                         and srt_b._exchange_plan.est_peak_bytes <= budget)
        bit_identical = (red_rows_b == base_red
                         and srt_rows_b == base_srt)

        # Interleaved warm reps, medians of 3.
        walls = {"one_shot": [], "planned": []}
        for _ in range(3):
            conf.dense_exchange = "all_to_all"
            conf.dense_hbm_budget = saved[1]
            _, _, r, s, w = pipeline()
            bit_identical &= (r == base_red and s == base_srt)
            walls["one_shot"].append(w)
            conf.dense_exchange = "auto"
            conf.dense_hbm_budget = budget
            _, _, r, s, w = pipeline()
            bit_identical &= (r == base_red and s == base_srt)
            walls["planned"].append(w)
        med = {leg: sorted(ws)[1] for leg, ws in walls.items()}
        result["warm_s"] = {leg: round(t, 4) for leg, t in med.items()}
        result["planned_vs_one_shot"] = round(
            med["planned"] / med["one_shot"], 3)

        # Streamed path, sizing: at the 1B-row shape (pure arithmetic —
        # planned_chunk_rows runs no device work) the planner's bounded
        # footprint sizes bigger chunks than the legacy 6x rule, so the
        # multi-pass fold pays fewer passes. (At toy scales the pow2
        # capacity rounding can quantize both rules onto the same
        # bucket — the 1B shape is the one the chunk count matters at.)
        from vega_tpu.tpu import mesh as mesh_lib

        n_shards = mesh_lib.default_mesh().size
        n_1b, rb_1b, budget_1b = 1_000_000_000, 8, saved[1]
        legacy_1b = planned_chunk_rows(n_1b, rb_1b, budget_1b)
        planned_1b = planned_chunk_rows(n_1b, rb_1b, budget_1b,
                                        n_shards=n_shards)
        legacy_passes = -(-n_1b // legacy_1b) if legacy_1b else -1
        planned_passes = -(-n_1b // planned_1b) if planned_1b else -1

        # Streamed path, execution: the fold stays exact at the
        # planner-derived sizing (proxy scale).
        conf.dense_exchange = "auto"
        n_stream = max(rows * 5, 1_000_000)
        stream_budget = n_stream * 4  # force streaming of the iota source
        conf.dense_hbm_budget = stream_budget
        s = ctx.dense_range(n_stream)
        streamed_ok = isinstance(s, StreamedDenseRDD)
        planned_chunks = s.n_chunks if streamed_ok else -1
        got = dict(s.map(lambda x: (x % 13, x))
                   .reduce_by_key(op="add").collect())
        conf.dense_hbm_budget = saved[1]
        exp = dict(ctx.dense_range(n_stream).map(lambda x: (x % 13, x))
                   .reduce_by_key(op="add").collect())
        streamed_ok = streamed_ok and got == exp
        result["stream"] = {
            "rows": n_stream, "budget_bytes": stream_budget,
            "chunks": planned_chunks,
            "sizing_1b": {
                "legacy_chunk_rows": legacy_1b, "legacy_passes":
                legacy_passes, "planned_chunk_rows": planned_1b,
                "planned_passes": planned_passes,
            },
        }

        result["accept"] = {
            "staged_on_device": bool(staged_on_device),
            "est_peak_le_budget": bool(est_le_budget),
            "bit_identical": bool(bit_identical),
            "streamed_exact": bool(streamed_ok),
            "stream_fewer_passes_1b": bool(
                0 < planned_passes < legacy_passes),
        }
    finally:
        conf.dense_exchange, conf.dense_hbm_budget = saved
        ctx.stop()

    print(json.dumps(result))


if __name__ == "__main__":
    main()
