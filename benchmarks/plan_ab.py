"""A/B + stage profile for the reduce exchange plans (round-4).

Answers the round-3 verdict's open question — do the lax.sort passes
dominate the warm exchange? — and A/Bs the two reduce plans:

  fused_sort:     ONE multi-key (bucket, key) lax.sort over all rows
  sort_partition: key-only lax.sort -> combine -> counting partition of
                  the combined rows (cheap VPU work when the combine
                  shrinks data, e.g. 20:1 at bench shapes)

Two measurements per plan:
  1) end-to-end warm reduce_by_key wall time (the real number);
  2) stage breakdown via separately-jitted pieces (sort / combine /
     partition / exchange collective / reduce-side merge) — indicative,
     not additive (fusion removes boundaries), but it shows which stage
     dominates and therefore whether Pallas kernel work should target
     the sort (verdict item 4).

Runs wherever jax lands (CPU mesh locally, the real chip on a TPU
machine). One JSON line. Usage: python benchmarks/plan_ab.py [rows]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TPU = os.environ.get("VEGA_PLAN_AB_TPU") == "1"
if not _TPU:
    from _cpu_mesh import force_cpu_mesh  # noqa: E402

    force_cpu_mesh(8)


def _timed(fn, *args, reps=3):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # warm/compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
    n_keys = max(1, rows // 20)  # bench-like 20:1 duplication

    import jax
    import jax.numpy as jnp
    import numpy as np

    import vega_tpu as v
    from vega_tpu.env import Env
    from vega_tpu.tpu import kernels, mesh as mesh_lib
    from vega_tpu.tpu.block import KEY, VALUE

    result = {"bench": "plan_ab", "rows": rows, "n_keys": n_keys,
              "backend": jax.default_backend()}

    ctx = v.Context("local")
    try:
        # --- end-to-end A/B (warm: second run of each shape) ------------
        plan_before = Env.get().conf.dense_rbk_plan
        for plan in ("fused_sort", "sort_partition"):
            Env.get().conf.dense_rbk_plan = plan

            def run():
                r = (ctx.dense_range(rows)
                     .map(lambda x, m=n_keys: (x % m, x))
                     .reduce_by_key(op="add"))
                return r.count()

            n0 = run()  # cold: compile + hints
            t0 = time.time()
            n1 = run()  # warm
            result[f"warm_s_{plan}"] = round(time.time() - t0, 4)
            assert n0 == n1 == n_keys
        # Restore the SHIPPED default ("auto" since round 5), not a
        # hardcoded plan: anything measured below must run what ships.
        Env.get().conf.dense_rbk_plan = plan_before

        # --- stage breakdown (per-shard shapes, jitted pieces) ----------
        mesh = mesh_lib.default_mesh()
        n = mesh.size
        per = -(-rows // max(n, 1))
        cap = 1 << max(7, (per - 1).bit_length())
        rng = np.random.RandomState(0)
        keys = jnp.asarray(rng.randint(0, n_keys, size=cap, dtype=np.int32))
        vals = jnp.asarray(rng.randint(0, 1 << 20, size=cap,
                                       dtype=np.int32))
        count = jnp.int32(per)
        cols = {KEY: keys, VALUE: vals}
        bucket = (kernels.hash32(keys) % jnp.uint32(max(n, 2))
                  ).astype(jnp.int32)

        stages = {
            "multikey_sort": jax.jit(
                lambda c, b, ct: kernels.bucket_key_sort(c, ct, b, KEY)),
            "key_sort": jax.jit(
                lambda c, ct: kernels.sort_by_column(c, ct, KEY)),
            "radix_key_sort": jax.jit(
                lambda c, ct: kernels.sort_by_column(c, ct, KEY,
                                                     impl="radix")),
            "radix4_key_sort": jax.jit(
                lambda c, ct: kernels.sort_by_column(c, ct, KEY,
                                                     impl="radix4")),
            "combine": jax.jit(
                lambda c, ct: kernels.segment_reduce_named(
                    c, ct, KEY, "add", presorted=True)),
            "partition": jax.jit(
                lambda c, b: kernels.partition_by_bucket(c, b, max(n, 2))),
        }
        result["stage_s_multikey_sort"] = round(
            _timed(stages["multikey_sort"], cols, bucket, count), 4)
        result["stage_s_key_sort"] = round(
            _timed(stages["key_sort"], cols, count), 4)
        result["stage_s_radix_key_sort"] = round(
            _timed(stages["radix_key_sort"], cols, count), 4)
        result["stage_s_radix4_key_sort"] = round(
            _timed(stages["radix4_key_sort"], cols, count), 4)
        sorted_cols = stages["key_sort"](cols, count)
        result["stage_s_combine_presorted"] = round(
            _timed(stages["combine"], sorted_cols, count), 4)
        comb_cols, comb_count = stages["combine"](sorted_cols, count)
        comb_bucket = (kernels.hash32(comb_cols[KEY])
                       % jnp.uint32(max(n, 2))).astype(jnp.int32)
        result["stage_s_partition_combined"] = round(
            _timed(stages["partition"], comb_cols, comb_bucket), 4)
        result["combined_rows_per_shard"] = int(comb_count)
    finally:
        ctx.stop()

    print(json.dumps(result))


if __name__ == "__main__":
    main()
