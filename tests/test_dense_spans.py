"""The dense tier's host spans (vega_tpu/tpu/spans.py): off they record
nothing; under a jax profiler session they tally launches, fetches, decodes
and pivots by name, never nest, and start over with each session."""

import threading

import numpy as np
import pytest

from test_dense_zipf import on_devices  # noqa: F401


@pytest.fixture()
def dctx():
    import vega_tpu as v

    context = v.Context("local", num_workers=2)
    yield context
    context.stop()


def _ind(v):
    return (v >= 3).astype("int32")


N, KEYS = 6000, 50


def _sources(ctx):
    keys = (np.arange(N, dtype=np.int64) * 7) % KEYS
    vals = (np.arange(N, dtype=np.float64) * 3) % 11
    return (ctx.dense_from_numpy(keys, vals),
            ctx.dense_from_numpy(np.arange(KEYS, dtype=np.int64),
                                 np.arange(KEYS, dtype=np.float64)))


LINEAGES = {
    "scan": lambda p, t: p.map_values(_ind).values_dense().sum(),
    "reduce_join_collect":
        lambda p, t: sorted(p.reduce_by_key(op="add").join(t).collect()),
    "collect_pairs": lambda p, t: sorted(p.collect()),
    "collect_values": lambda p, t: sorted(p.values_dense().collect()),
    "group_by_key_collect":
        lambda p, t: sorted((k, sorted(vs))
                            for k, vs in p.group_by_key().collect()),
    "cogroup_collect":
        lambda p, t: sorted((k, (sorted(a), sorted(b)))
                            for k, (a, b) in p.cogroup(t).collect()),
    "sort_collect_arrays":
        lambda p, t: [c.tolist() for c in
                      p.sort_by_key().collect_arrays().values()],
    "take_ordered": lambda p, t: p.take_ordered(7),
    "top_values": lambda p, t: p.values_dense().top(5),
}


@pytest.mark.parametrize("name", ["scan", "reduce_join_collect"])
def test_off_records_nothing(dctx, name):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    before = spans.session()
    LINEAGES[name](pairs, table)
    with spans.span("fetch", nbytes=8) as sp:
        assert sp.on is False
    assert spans.session() == before
    assert spans.nested() == 0


def test_on_tallies_a_scan(dctx, session):
    from vega_tpu.tpu import dense_rdd, spans

    pairs, table = _sources(dctx)
    expected = LINEAGES["scan"](pairs, table)  # warm: mints two programs
    mints = dense_rdd.program_mints()
    with session:
        assert LINEAGES["scan"](pairs, table) == expected
    tally = spans.session()
    assert tally["launch"]["count"] == 2
    assert {k: v["count"] for k, v in tally["launch"]["by_kind"].items()} \
        == {"narrow": 1, "named_reduce": 1}
    assert tally["fetch"]["count"] == 1 and tally["fetch"]["bytes"] > 0
    assert tally["launch"]["seconds"] > 0 and tally["fetch"]["seconds"] > 0
    assert "decode" not in tally and "pivot" not in tally
    # wrapping a program at mint mints nothing more
    assert dense_rdd.program_mints() == mints
    # once the session has ended the tally stops growing
    LINEAGES["scan"](pairs, table)
    assert spans.session() == tally


def test_on_tallies_decode_and_pivot(dctx, session):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    with session:
        rows = pairs.reduce_by_key(op="add").join(table).collect()
    tally = spans.session()
    assert len(rows) == KEYS
    assert tally["decode"]["seconds"] > 0 and tally["decode"]["bytes"] > 0
    assert tally["pivot"]["seconds"] > 0 and tally["pivot"]["count"] == 1
    assert sorted(tally["pivot"]) == ["by_kind", "bytes", "count", "seconds"]
    assert tally["fetch"]["count"] >= 1 and tally["launch"]["count"] >= 2


def test_collect_arrays_is_one_fetch_across_shards(dctx, on_devices, session):
    """to_numpy reads every shard's own buffer of every column in ONE
    host_get: a 4-shard block is one `fetch` span (one blocking round trip)
    of the block's whole bytes, and one `decode` of its valid rows."""
    from vega_tpu.tpu import spans

    on_devices(4)
    keys = (np.arange(N, dtype=np.int64) * 2654435761) % (2**40)
    vals = np.arange(N, dtype=np.float64)
    pairs = dctx.dense_from_numpy(keys, vals)
    blk = pairs.block()
    assert blk.n_shards == 4 and sorted(blk.cols) == ["k", "k.lo", "v"]
    with session:
        cols = pairs.collect_arrays()
    assert np.array_equal(cols["k"], keys)
    assert np.array_equal(cols["v"], vals.astype(np.float32))
    tally = spans.session()
    assert tally["fetch"]["count"] == 1
    assert tally["fetch"]["bytes"] == blk.nbytes
    assert tally["decode"]["count"] == 1
    assert tally["decode"]["bytes"] == N * (8 + 4)
    assert "launch" not in tally and spans.nested() == 0


@pytest.mark.parametrize("name", sorted(LINEAGES))
def test_spans_are_flat_and_change_no_result(dctx, session, name):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    expected = LINEAGES[name](pairs, table)
    with session:
        got = LINEAGES[name](pairs, table)
    assert got == expected
    assert spans.nested() == 0
    tally = spans.session()
    assert tally["fetch"]["count"] >= 1  # a source's collect launches none
    spanned = sum(acc["seconds"] for acc in tally.values())
    assert 0 < spanned < 60


def test_a_nested_span_is_counted(session):
    """The counter the flatness tests hold at 0 does count."""
    from vega_tpu.tpu import spans

    before = spans.nested()
    try:
        with session:
            with spans.span("decode"):
                with spans.span("pivot"):
                    pass
        assert spans.nested() == before + 1
    finally:
        spans._nested = before


def test_depth_is_per_thread(session):
    from vega_tpu.tpu import spans

    inside, release = threading.Event(), threading.Event()

    def hold():
        with spans.span("decode"):
            inside.set()
            release.wait(10)

    spans.new_session()  # the last test's session may have had no span after it
    with session:
        t = threading.Thread(target=hold)
        t.start()
        assert inside.wait(10)
        with spans.span("pivot"):  # another thread's span is open
            pass
        release.set()
        t.join(10)
    assert spans.nested() == 0
    assert spans.session()["decode"]["count"] == 1
    assert spans.session()["pivot"]["count"] == 1


@pytest.mark.parametrize("how", ["start_trace", "ctx.profiler"])
def test_a_second_session_starts_empty(dctx, session, tmp_path, how):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    LINEAGES["reduce_join_collect"](pairs, table)
    LINEAGES["scan"](pairs, table)
    if how == "start_trace":
        with session:
            LINEAGES["reduce_join_collect"](pairs, table)
        assert "pivot" in spans.session()
        LINEAGES["scan"](pairs, table)  # a span sees that no session runs
        with session:
            LINEAGES["scan"](pairs, table)
    else:  # back to back, no span between: profiler() marks the new one
        with dctx.profiler(str(tmp_path / "a")):
            LINEAGES["reduce_join_collect"](pairs, table)
        assert "pivot" in spans.session()
        with dctx.profiler(str(tmp_path / "b")):
            LINEAGES["scan"](pairs, table)
    tally = spans.session()
    assert sorted(tally) == ["fetch", "fingerprint", "launch"]
    assert tally["launch"]["count"] == 2 and tally["fetch"]["count"] == 1


def test_programs_table(dctx):
    from vega_tpu.tpu import dense_rdd, spans

    pairs, _table = _sources(dctx)
    before = spans.programs()
    salt = dense_rdd.program_mints() + 12345

    def fresh(v):  # a closure no other test has fingerprinted
        return v + salt

    pairs.map_values(fresh).values_dense().sum()
    after = spans.programs()
    assert after["narrow"]["mints"] == before.get(
        "narrow", {"mints": 0})["mints"] + 1
    assert after["narrow"]["first_call_s"] > before.get(
        "narrow", {"first_call_s": 0.0})["first_call_s"]
    assert all(p["mints"] >= 1 and p["first_call_s"] > 0
               for p in after.values())
    assert dense_rdd.program_mints() == sum(
        p["mints"] for p in after.values()) == salt - 12345 + 1 + (
            after["named_reduce"]["mints"]
            - before.get("named_reduce", {"mints": 0})["mints"])


def test_first_call_is_timed_once_under_threads():
    """Two task threads that both make a minted program's first call: one
    of them is timed, and each call reaches the program."""
    from vega_tpu.tpu import dense_rdd, spans

    kind = "test_first_call_once"
    inside, release = threading.Event(), threading.Event()
    calls = []

    def prog(x):
        calls.append(x)
        inside.set()
        release.wait(10)
        return x

    launch = dense_rdd._spanned_program(kind, prog)
    spans.program_minted(kind)
    try:
        t = threading.Thread(target=launch, args=(1,))
        t.start()
        assert inside.wait(10)  # the first call is in flight
        threading.Timer(0.05, release.set).start()
        assert launch(2) == 2  # untimed: the first is taken
        t.join(10)
        assert sorted(calls) == [1, 2]
        first_call_s = spans.programs()[kind]["first_call_s"]
        assert first_call_s > 0
        launch(3)
        assert spans.programs()[kind]["first_call_s"] == first_call_s
    finally:
        spans._programs.pop(kind)


def test_host_only_summary_imports_no_jax():
    """`metrics_summary()["dense_spans"]` of a job that never touched the
    dense tier is empty, and reading it does not import jax."""
    import subprocess
    import sys

    code = (
        "import sys, vega_tpu as v\n"
        "with v.Context('local', num_workers=2) as ctx:\n"
        "    assert ctx.parallelize(range(10), 2).map(lambda x: x + 1)"
        ".collect()[-1] == 10\n"
        "    dense = ctx.metrics_summary()['dense_spans']\n"
        "assert dense == {'session': {}, 'programs': {}}, dense\n"
        "assert 'vega_tpu.tpu.spans' in sys.modules\n"
        "assert 'jax' not in sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_metrics_summary_has_both(dctx, session):
    from vega_tpu.tpu import spans

    pairs, table = _sources(dctx)
    with session:
        LINEAGES["scan"](pairs, table)
    dense = dctx.metrics_summary()["dense_spans"]
    assert dense == {"session": spans.session(),
                     "programs": spans.programs()}
    assert dense["session"]["launch"]["count"] == 2
    assert dense["programs"]["narrow"]["mints"] >= 1
    # copies: an edit does not reach the tally
    dense["session"]["launch"]["count"] = 99
    assert spans.session()["launch"]["count"] == 2


def test_exchange_stage_duration_is_monotonic(dctx):
    """StageCompleted.duration_s of a dense exchange comes from
    time.perf_counter: never negative, and within the action's wall."""
    import time

    from vega_tpu.scheduler import events as ev

    seen = []

    class Listener(ev.Listener):
        def on_event(self, event):
            if isinstance(event, ev.StageCompleted):
                seen.append(event.duration_s)

    dctx.bus.add_listener(Listener())
    pairs, _table = _sources(dctx)
    t0 = time.perf_counter()
    pairs.reduce_by_key(op="add").collect()
    wall = time.perf_counter() - t0
    dctx.bus.flush()
    assert seen and all(0 <= d <= wall for d in seen)


@pytest.mark.parametrize("devices, exchange, rounds", [
    (4, None, 1),  # the planner's one-shot all_to_all
    (4, "ring", 3),  # the mesh's n - 1
    (8, "ring", 7),
    (4, "staged", 1),  # forced: the largest group that fits, one round here
    (1, None, None),  # one shard plans nothing and counts nothing
])
def test_exchange_plan_rounds_an_action(dctx, on_devices, session, devices,
                                        exchange, rounds):
    """`exchange_plan_rounds` adds the resolved plan's rounds once a launch:
    a sort's action reads which collective program ran; cold (sized by the
    histogram) and warm (hinted, the launch deferred) alike."""
    from vega_tpu.tpu import spans

    on_devices(devices)
    keys = (np.arange(N, dtype=np.int64) * 2654435761) % (2**40)
    pairs = dctx.dense_from_numpy(keys, np.arange(N, dtype=np.float64))
    for _run in ("cold", "warm"):
        spans.new_session()
        with session:
            node = pairs.sort_by_key(exchange=exchange)
            cols = node.collect_arrays()
        assert np.array_equal(cols["k"], np.sort(keys))
        tally = spans.session()
        assert tally["exchange"]["count"] == 1
        if rounds is None:
            assert "exchange_plan_rounds" not in tally
            assert node._exchange_plan is None
        else:
            assert tally["exchange_plan_rounds"] == {
                "count": rounds, "seconds": 0.0, "bytes": 0, "by_kind": {}}
            assert node._exchange_plan.rounds == rounds
        assert spans.nested() == 0
    # off, the counter records nothing
    before = spans.session()
    pairs.sort_by_key(exchange=exchange).collect_arrays()
    assert spans.session() == before
