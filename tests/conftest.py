"""Test harness config.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding is
exercised without TPU hardware: force_cpu_mesh adds the device-count flag
and pins jax_platforms before the backend initializes (JAX_PLATFORMS=cpu in
the environment is enough for the platform; the flag is what conftest
adds). The driver separately dry-run-compiles the multi-chip path via
__graft_entry__.dryrun_multichip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cpu_mesh import force_cpu_mesh  # noqa: E402

# Must precede backend initialization (first jax.devices()/jit call).
# VEGA_TPU_HW_TESTS=1 is the hardware tier: set it on a machine with a TPU
# so @pytest.mark.tpu tests run on the real chip; everything else keeps the
# virtual CPU mesh.
_HW = os.environ.get("VEGA_TPU_HW_TESTS") == "1"
if not _HW:
    force_cpu_mesh(8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: needs real TPU hardware (run on the chip with "
        "VEGA_TPU_HW_TESTS=1)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection test (vega_tpu/faults.py) — "
        "kills/wedges workers, drops fetches, corrupts buckets; run the "
        "full set via scripts/chaos.sh")
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the tier-1 "
        "timing budget (scripts/t1.sh runs -m 'not slow')")


def pytest_collection_modifyitems(config, items):
    if _HW:
        # Hardware run: ONLY the tpu tier may run — the rest of the
        # suite assumes the 8-virtual-device CPU mesh, which was not
        # forced. Self-contained even if the caller forgot `-m tpu`.
        skip_cpu = pytest.mark.skip(reason="CPU-mesh test: not run under "
                                    "VEGA_TPU_HW_TESTS=1")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip_cpu)
        return
    skip_hw = pytest.mark.skip(reason="real-TPU test: needs "
                               "VEGA_TPU_HW_TESTS=1 on a machine with a TPU")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_hw)


def pytest_sessionfinish(session, exitstatus):
    # Runtime lock-order sanitizer (vega_tpu/lint/sync_witness.py): under
    # VEGA_TPU_DEBUG_SYNC=1 every named lock records acquisition order and
    # raises on inversion AT the inverting acquire; this end-of-session
    # check additionally fails the run if an in-place raise was swallowed
    # by a broad handler somewhere (the VG005 blindness, dynamically).
    from vega_tpu.lint import sync_witness

    if sync_witness.enabled():
        sync_witness.check_clean()


def pytest_terminal_summary(terminalreporter):
    from vega_tpu.lint import sync_witness

    if sync_witness.enabled():
        st = sync_witness.witness().stats()
        roles = ", ".join(f"{r}({len(t)})"
                          for r, t in sorted(st["roles"].items())) or "none"
        terminalreporter.write_line(
            f"sync-witness: {st['locks']} named locks, {st['edges']} "
            f"order edges, {len(st['inversions'])} inversion(s); roles "
            f"observed: {roles}; "
            f"{len(st['role_violations'])} role violation(s)")


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """Free every compiled program when a test module ends.

    An XLA:CPU executable holds tens of memory mappings and the suite
    compiles thousands of programs, all kept alive by the structural
    program cache and jax's jit caches. Run in ONE process on a
    multi-core machine, tier-1 reached vm.max_map_count (65530) two
    thirds of the way through and died inside XLA (SIGSEGV in
    deserialize_executable or SIGABRT in backend_compile_and_load,
    always at the same test). No test depends on a program compiled by
    another module."""
    yield
    import jax

    from vega_tpu.tpu import dense_rdd, spans

    dense_rdd._PROGRAM_CACHE.clear()
    spans.forget_lowered()  # each keeps its program's executable alive
    jax.clear_caches()


@pytest.fixture()
def ctx():
    """Fresh local Context per test. The Env (shuffle store, trackers) is a
    process singleton like the reference's (src/env.rs:38-40), so contexts
    must not overlap — function scope guarantees that."""
    import vega_tpu as v

    context = v.Context("local", num_workers=4)
    yield context
    context.stop()


@pytest.fixture()
def session(tmp_path):
    """start() opens a real jax profiler session (host tracer only: the
    Python tracer would slow every call), stop() ends it; whatever is left
    open is stopped at teardown."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    running = []

    class Session:
        def start(self):
            jax.profiler.start_trace(str(tmp_path / f"t{len(running)}"),
                                     profiler_options=opts)
            running.append(True)

        def stop(self):
            running.pop()
            jax.profiler.stop_trace()

        def __enter__(self):
            self.start()

        def __exit__(self, *exc):
            self.stop()

    yield Session()
    if running:
        jax.profiler.stop_trace()
