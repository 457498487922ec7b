"""Offline TPU lowering tier: every core device program must LOWER for
the tpu platform — validated on the CPU mesh via jax.export, no hardware.

Chip time is budgeted; a program that traces and runs on the CPU mesh but
fails Mosaic/TPU lowering (a Pallas kernel using an unsupported op, a
collective layout XLA:TPU rejects) would otherwise only surface in a chip
run. These tests catch that class offline: export with platforms=["tpu"]
runs the full TPU lowering pipeline (including Pallas->Mosaic kernel
lowering into tpu_custom_call payloads).

Complement, not substitute, for tests/test_tpu_hw.py: lowering proves the
compiler accepts the program; the hw tier proves the chip computes the
right answer.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from vega_tpu.tpu import block as block_lib
from vega_tpu.tpu import kernels
from vega_tpu.tpu import mesh as mesh_lib
from vega_tpu.tpu.block import KEY, KEY_LO, VALUE

CAP = 1024
N = 8


def _export_sharded(prog, n_in, n_out, args):
    mesh = mesh_lib.default_mesh()
    sp = P(mesh_lib.SHARD_AXIS)
    from vega_tpu.tpu import compat

    f = jax.jit(compat.shard_map(prog, mesh=mesh, in_specs=(sp,) * n_in,
                                 out_specs=(sp,) * n_out))
    exp = jax.export.export(f, platforms=["tpu"])(*args)
    m = exp.mlir_module()
    assert len(m) > 0
    return m


def _pair_args():
    counts = jnp.full((N,), 900, jnp.int32)
    keys = jnp.arange(N * CAP, dtype=jnp.int32) % 500
    vals = jnp.ones(N * CAP, jnp.int32)
    return counts, keys, vals


def test_lowering_rbk_fused_sort():
    def prog(counts, keys, vals):
        cols = {KEY: keys, VALUE: vals}
        count = counts[0]
        bucket = (kernels.hash32(keys) % jnp.uint32(N)).astype(jnp.int32)
        bucket = jnp.where(kernels.valid_mask(CAP, count), bucket, N)
        cols, bucket = kernels.bucket_key_sort(cols, bucket, KEY)
        cols, count = kernels.segment_reduce_named(
            cols, count, KEY, "add", presorted=True)
        bucket = (kernels.hash32(cols[KEY])
                  % jnp.uint32(N)).astype(jnp.int32)
        out, n2, ovf = kernels.bucket_exchange(
            cols, count, bucket, N, 256, CAP, pregrouped=True)
        return out[KEY], out[VALUE], n2.reshape(1), ovf.reshape(1)

    _export_sharded(prog, 3, 4, _pair_args())


def test_lowering_float_add_long_run():
    """A shard that can hold a run over LONG_RUN_ROWS: the float add's
    `lax.cond` and its blocked pairwise branch lower under shard_map."""
    cap = 2 * kernels.LONG_RUN_ROWS

    def prog(counts, keys, vals):
        out, n = kernels.segment_reduce_named(
            {KEY: keys, VALUE: vals}, counts[0], KEY, "add")
        return out[KEY], out[VALUE], n.reshape(1)

    m = _export_sharded(prog, 3, 3, (
        jnp.full((N,), cap - 5, jnp.int32), jnp.zeros(N * cap, jnp.int32),
        jnp.ones(N * cap, jnp.float32)))
    assert "stablehlo.case" in m or "stablehlo.if" in m


def test_lowering_ring_exchange():
    from vega_tpu.tpu.ring import ring_exchange

    def prog(counts, keys, vals):
        cols = {KEY: keys, VALUE: vals}
        count = counts[0]
        bucket = (kernels.hash32(keys) % jnp.uint32(N)).astype(jnp.int32)
        bucket = jnp.where(kernels.valid_mask(CAP, count), bucket, N)
        out, n2, ovf = ring_exchange(cols, count, bucket, N, 256, CAP)
        return out[KEY], out[VALUE], n2.reshape(1), ovf.reshape(1)

    _export_sharded(prog, 3, 4, _pair_args())


def test_lowering_wide_int64_scan():
    from vega_tpu.tpu.dense_rdd import _SOVF, _named_wide_combine

    vlo = block_lib.lo_of(VALUE)

    def prog(counts, keys, hi, lo):
        count = counts[0]
        cols = {KEY: keys, VALUE: hi, vlo: lo,
                _SOVF: jnp.zeros((CAP,), jnp.int32)}
        combine = _named_wide_combine(
            "add", [VALUE, vlo, _SOVF], {VALUE: vlo}, ovf_name=_SOVF)
        out, n2 = kernels.segment_reduce_sorted(
            cols, count, KEY, combine, presorted=False)
        flag = jnp.any(out[_SOVF] != 0)
        return out[KEY], out[VALUE], out[vlo], flag.reshape(1)

    counts = jnp.full((N,), 900, jnp.int32)
    keys = jnp.arange(N * CAP, dtype=jnp.int32) % 300
    hi = jnp.ones(N * CAP, jnp.int32)
    lo = jnp.ones(N * CAP, jnp.int32)
    _export_sharded(prog, 4, 4, (counts, keys, hi, lo))


def test_lowering_merge_join_expand():
    def prog(counts, keys, vals):
        count = counts[0]
        lcols = {KEY: keys, VALUE: vals}
        rcols = {KEY: keys, VALUE: vals}
        joined, jcount, jtotal = kernels.merge_join_expand(
            lcols, count, rcols, count, KEY, CAP)
        return (joined[KEY], joined[VALUE], joined[f"r_{VALUE}"],
                jcount.reshape(1), jtotal.reshape(1))

    _export_sharded(prog, 3, 5, _pair_args())


def _ops(jaxpr, name, times=1):
    """Operations of one primitive a jaxpr runs: one inside a scan counts
    once a step (jnp.searchsorted is a scan of log2(n) steps, a gather
    each); one inside a cond counts in every branch that holds it."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += times
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _ops(sub, name, inner)
    return n


def _gathers(jaxpr, times=1):
    return _ops(jaxpr, "gather", times)


@pytest.mark.parametrize("wide", [False, True], ids=["one_word", "two_word"])
@pytest.mark.parametrize("outer", [False, True], ids=["inner", "outer"])
def test_merge_join_expand_gathers_do_not_grow_with_capacity(wide, outer):
    """No chip needed: positions in sorted data come from a merge and
    scans, so the join holds as many gathers at 2^16 rows as at 2^10. A
    binary search per row holds log2(capacity) more each time."""
    def count(cap):
        def prog(n, keys, lo, vals):
            cols = {KEY: keys, VALUE: vals}
            if wide:
                cols[KEY_LO] = lo
            return kernels.merge_join_expand(
                cols, n, dict(cols), n, KEY, cap, outer=outer,
                lo_name=KEY_LO if wide else None)

        col = jax.ShapeDtypeStruct((cap,), jnp.int32)
        closed = jax.make_jaxpr(prog)(
            jax.ShapeDtypeStruct((), jnp.int32), col, col, col)
        return _gathers(closed.jaxpr)

    small, large = count(2**10), count(2**16)
    assert small == large
    # the sorts' permutations and the output columns: a handful, not 3 x 16
    assert large <= 16


@pytest.mark.parametrize("deep", [0, 1], ids=["flat_columns", "one_2d"])
@pytest.mark.parametrize("kernel", [
    "sort_one_word", "sort_one_word_descending", "sort_two_word",
    "sort_two_word_descending", "bucket_one_word", "bucket_two_word"])
def test_sorts_carry_columns_and_gather_only_2d(kernel, deep):
    """No chip needed: a sort's 1-D columns are operands of its one
    lax.sort, so the jaxpr holds no gather at all (and one sort); a column
    with more than one dimension cannot be an operand, and the block that
    has one holds exactly that column's gather."""
    wide = "two_word" in kernel

    def prog(n, bucket, keys, lo, ints, floats, flags, matrix):
        cols = {KEY: keys, VALUE: ints, "f": floats, "b": flags}
        if wide:
            cols[KEY_LO] = lo
        if deep:
            cols["m"] = matrix
        lo_name = KEY_LO if wide else None
        if kernel.startswith("bucket"):
            return kernels.bucket_key_sort(cols, bucket, KEY,
                                           lo_name=lo_name)
        return kernels.sort_by_column(
            cols, n, KEY, descending=kernel.endswith("descending"),
            lo_name=lo_name)

    col = jax.ShapeDtypeStruct((CAP,), jnp.int32)
    jaxpr = jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((), jnp.int32), col, col, col, col,
        jax.ShapeDtypeStruct((CAP,), jnp.float32),
        jax.ShapeDtypeStruct((CAP,), jnp.bool_),
        jax.ShapeDtypeStruct((CAP, 3), jnp.float32)).jaxpr
    assert _gathers(jaxpr) == deep
    assert sum(e.primitive.name == "sort" for e in jaxpr.eqns) == 1


@pytest.mark.parametrize("wide", [False, True], ids=["one_word", "two_word"])
@pytest.mark.parametrize("op,seg_scatter", [("add", "scatter-add"),
                                            ("min", "scatter-min")])
def test_segment_reduce_named_writes_each_column_once(op, seg_scatter, wide):
    """No chip needed: every output column of the named reduce is one
    scatter. A key word is compacted by the rows that start a segment, a
    value column is the segment op's scatter and rows past the last segment
    are cleared elementwise; nothing is gathered, no `nonzero` counts
    (it is a scatter-add of its own) and nothing is compacted afterwards.
    8,192 rows, so the float add's long-run branch is in the program."""
    cap = 2 * kernels.LONG_RUN_ROWS

    def prog(n, keys, lo, floats, matrix):
        cols = {KEY: keys, VALUE: floats, "m": matrix}
        if wide:
            cols[KEY_LO] = lo
        return kernels.segment_reduce_named(
            cols, n, KEY, op, presorted=True,
            lo_name=KEY_LO if wide else None)

    col = jax.ShapeDtypeStruct((cap,), jnp.int32)
    jaxpr = jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((), jnp.int32), col, col,
        jax.ShapeDtypeStruct((cap,), jnp.float32),
        jax.ShapeDtypeStruct((cap, 3), jnp.float32)).jaxpr
    assert _gathers(jaxpr) == 0
    assert _ops(jaxpr, "scatter") == (2 if wide else 1)  # the key words
    scatters = {nm: _ops(jaxpr, nm) for nm in
                ("scatter-add", "scatter-min", "scatter-max", "scatter-mul")}
    assert scatters.pop(seg_scatter) == 2  # the two value columns
    assert not any(scatters.values())


def test_lowering_sort_carries_mixed_columns():
    """A sort whose operands mix int32, float32, bool and uint8 columns
    with a 2-D column behind it lowers for the tpu platform."""
    def prog(counts, keys, vals):
        cols = {KEY: keys, VALUE: vals, "f": vals.astype(jnp.float32),
                "b": vals > 0, "u": vals.astype(jnp.uint8),
                "m": jnp.stack([vals, keys], axis=1)}
        out = kernels.sort_by_column(cols, counts[0], KEY, descending=True)
        return tuple(out[nm] for nm in cols)

    _export_sharded(prog, 3, 6, _pair_args())


def test_lowering_range_sort():
    def prog(bounds, counts, keys, vals):
        count = counts[0]
        cols = {KEY: keys, VALUE: vals}
        bucket = kernels.range_bucket(bounds, keys, True)
        bucket = jnp.where(kernels.valid_mask(CAP, count), bucket, N)
        out, n2, ovf = kernels.bucket_exchange(
            cols, count, bucket, N, 512, CAP)
        out = kernels.sort_by_column(out, n2, KEY)
        return out[KEY], out[VALUE], n2.reshape(1), ovf.reshape(1)

    mesh = mesh_lib.default_mesh()
    sp = P(mesh_lib.SHARD_AXIS)
    from vega_tpu.tpu import compat

    f = jax.jit(compat.shard_map(
        prog, mesh=mesh, in_specs=(P(), sp, sp, sp),
        out_specs=(sp,) * 4))
    bounds = jnp.arange(N - 1, dtype=jnp.int32) * 64
    counts, keys, vals = _pair_args()
    exp = jax.export.export(f, platforms=["tpu"])(bounds, counts, keys,
                                                  vals)
    assert len(exp.mlir_module()) > 0


def test_lowering_composed_partition_carries_mosaic_kernel():
    """The COMPOSED exchange program exported for tpu must contain the
    Pallas rank kernel (lax.platform_dependent selects it at lowering):
    a trace-time backend dispatch would export the XLA fallback and the
    offline tier would never see the program the chip actually runs."""
    def prog(counts, keys, vals):
        cols = {KEY: keys, VALUE: vals}
        count = counts[0]
        bucket = (kernels.hash32(keys) % jnp.uint32(N)).astype(jnp.int32)
        bucket = jnp.where(kernels.valid_mask(CAP, count), bucket, N)
        out, counts_to, _ = kernels._group_by_bucket(cols, bucket, N)
        return out[KEY], out[VALUE], counts_to

    m = _export_sharded(prog, 3, 3, _pair_args())
    assert "tpu_custom_call" in m

    # the low-memory flavor (ring_exchange's grouping) carries it too
    def prog_lm(counts, keys, vals):
        cols = {KEY: keys, VALUE: vals}
        count = counts[0]
        bucket = (kernels.hash32(keys) % jnp.uint32(N)).astype(jnp.int32)
        bucket = jnp.where(kernels.valid_mask(CAP, count), bucket, N)
        out, counts_to, _ = kernels._group_by_bucket(
            cols, bucket, N, prefer_low_memory=True)
        return out[KEY], out[VALUE], counts_to

    m = _export_sharded(prog_lm, 3, 3, _pair_args())
    assert "tpu_custom_call" in m


def test_lowering_pallas_hash_kernel():
    from vega_tpu.tpu.pallas_kernels import hash_bucket_pallas

    x = jnp.arange(2048, dtype=jnp.int32)
    exp = jax.export.export(
        jax.jit(lambda k: hash_bucket_pallas(k, N)), platforms=["tpu"],
    )(x)
    m = exp.mlir_module()
    # the kernel must actually have gone through Mosaic
    assert "tpu_custom_call" in m


def test_lowering_wide_key_join_search():
    def prog(counts, keys, vals):
        count = counts[0]
        hi, lo = keys, vals  # stand-ins with the right dtypes
        idx = kernels.searchsorted2(hi, lo, hi, lo, "left")
        return (idx.astype(jnp.int32),)

    _export_sharded(prog, 3, 1, _pair_args())


@pytest.mark.skipif(
    os.environ.get("VEGA_LOWERING_INPROC") != "1",
    reason="runs via test_lowering_real_pipeline_programs_isolated (an "
           "XLA:CPU compiler SIGSEGV reproduces only when this compile+"
           "export sweep runs late in the full in-process suite; a "
           "pristine subprocess compiles it reliably)")
def test_lowering_real_pipeline_programs(monkeypatch):
    """Export THE actual programs the dense tier builds — not hand-built
    reconstructions: run a representative pipeline matrix on the CPU
    mesh with a _shard_program hook that records each jitted program and
    its first-call args, then export every one for tpu. Catches Mosaic /
    XLA:TPU lowering regressions in the exact composed programs
    production runs (fused chains, segment reduces, histograms, deferred
    exchanges, topk, zip, union — whatever the pipelines built)."""
    import vega_tpu as v
    from vega_tpu.tpu import dense_rdd as dr

    recorded = []
    orig = dr._shard_program

    def wrapping(mesh, fn, in_specs, out_specs):
        prog = orig(mesh, fn, in_specs, out_specs)

        def wrapper(*args):
            if not hasattr(wrapper, "_args"):
                wrapper._args = args
                recorded.append(wrapper)
            return prog(*args)

        wrapper._prog = prog
        return wrapper

    monkeypatch.setattr(dr, "_shard_program", wrapping)
    monkeypatch.setattr(dr, "_PROGRAM_CACHE", {})

    ctx = v.Context("local", num_workers=2)
    try:
        def reduce_once():
            kv = ctx.dense_range(20_000).map(lambda x: (x % 211, x * 1.0))
            return kv, kv.reduce_by_key(op="add")

        kv, red = reduce_once()
        table = ctx.dense_from_numpy(np.arange(211, dtype=np.int32),
                                     np.arange(211, dtype=np.float32))
        assert red.join(table).count() == 211
        # Warm rerun: the deferred (hinted-capacity) launch lowers too.
        _, red_warm = reduce_once()
        assert dict(red_warm.collect())
        assert len(kv.sort_by_key(ascending=False).take(5)) == 5
        kv.group_by_key().collect_grouped()
        assert len(kv.take_ordered(5)) == 5
        # wide int64 values + overflow tracking
        wide = ctx.dense_from_numpy(
            np.array([1, 1, 2], dtype=np.int64),
            np.array([2**40, 2**41, 7], dtype=np.int64))
        wide.reduce_by_key(op="add").collect()
        bare = ctx.dense_from_numpy(np.array([2**40, 5], dtype=np.int64))
        bare.sum()
    finally:
        ctx.stop()

    assert len(recorded) >= 12, len(recorded)
    failures = []
    for w in recorded:
        try:
            jax.export.export(w._prog, platforms=["tpu"])(*w._args)
        except Exception as e:  # noqa: BLE001 — collect all failures
            failures.append(f"{type(e).__name__}: {str(e)[:200]}")
    assert not failures, "\n".join(failures)


def test_lowering_real_pipeline_programs_isolated():
    """Run the real-pipeline export sweep in a PRISTINE subprocess.

    Round 5 reproduced an XLA:CPU compiler segfault (inside
    backend_compile_and_load, with and without the persistent compile
    cache) that occurs ONLY when the sweep's compile+export load runs
    late in the full in-process suite — standalone and small-combination
    runs pass every time. Process isolation keeps the coverage while
    converting any residual compiler crash into a clean, attributable
    failure instead of killing the whole pytest process."""
    import subprocess
    import sys

    env = dict(os.environ, VEGA_LOWERING_INPROC="1")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         f"{__file__}::test_lowering_real_pipeline_programs"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (
        f"isolated lowering sweep failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout[-2000:]}\nstderr:\n{proc.stderr[-2000:]}")
