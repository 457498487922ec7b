"""The readers of the program's spans and counters (vega_tpu/tpu/spans.py)
and of the collectives: each on a small hand-made `obs`, the expected number
and `None` where there is nothing to read; then every cell's traced
rehearsal prints them."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT, load

NEW = ["result_fetch_s_per_action", "result_decode_s_per_action",
       "collect_pivot_s_per_action", "launches_per_action",
       "host_syncs_per_action", "unspanned_host_s_per_action",
       "program_first_call_s", "device_idle_unspanned_share",
       "collective_s_per_action"]

A2A = "%all-to-all.3 = (s32[8]{0}, f32[8]{0}) all-to-all(%a, %b), replica_groups={}"
FUSION = "%fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f"
WHILE = "%while.2 = (s32[]) while(%t), condition=%c, body=%b"

# A window of 10 s and two actions. The first plane is busy in [0.5, 2.0] and
# [6.5, 6.8]; the host is in a fetch in [1, 3] and [6, 7], then in a pivot
# in [7, 9].
EVENTS = {
    "host": [["perfbench:window", 0.0, 10.0],
             ["perfbench:action call", 0.2, 9.0],
             ["vega:fetch", 1.0, 2.0], ["vega:fetch", 6.0, 1.0],
             ["vega:pivot", 7.0, 2.0],
             ["np.asarray(jax.Array)", 1.0, 2.0]],
    "devices": {
        "/device:TPU:0": [[FUSION, 0.5, 1.5], [A2A, 6.5, 0.3]],
        # a while around its body: an all-to-all of 1 s with a fusion of
        # 0.5 s inside it
        "/device:TPU:1": [[WHILE, 3.9, 1.2], [A2A, 4.0, 1.0],
                          [FUSION, 4.5, 0.5]],
    },
}


@pytest.fixture(scope="module")
def read():
    return {n: load(os.path.join(BENCH, "metrics", n + ".py")).read
            for n in NEW}


@pytest.fixture(scope="module")
def obs():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    return {"actions": 2, "action_walls": [4.0, 5.0], "events": EVENTS,
            "trace": tr.reduce_events(EVENTS)}


@pytest.fixture()
def spans():
    sys.path.insert(0, ROOT)
    try:
        from vega_tpu.tpu import spans as mod
    finally:
        sys.path.remove(ROOT)
    return mod


EVENT_READERS = {
    # fetch [1, 3] idles in [2, 3]; fetch [6, 7] idles but for [6.5, 6.8]
    "result_fetch_s_per_action": (1.0 + 0.7) / 2,
    # operations and spans cover [0.5, 3] and [6, 9]: 4.5 s of 10 are bare
    "device_idle_unspanned_share": 45.0,
    # self seconds 0.3 on one plane, 0.5 on the other (the fusion inside it
    # taken off), averaged over the planes, over two actions
    "collective_s_per_action": (0.3 + 0.5) / 2 / 2,
}


@pytest.mark.parametrize("name", sorted(EVENT_READERS))
def test_event_readers(read, obs, name):
    assert read[name](obs) == pytest.approx(EVENT_READERS[name], abs=1e-9)
    # a program that opens no span, on one chip that runs no collective
    bare = {"host": [h for h in EVENTS["host"] if not h[0].startswith("vega:")],
            "devices": {"/device:TPU:0": [[FUSION, 0.5, 1.5]]}}
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    assert read[name](dict(obs, events=bare,
                           trace=tr.reduce_events(bare))) is None
    # no window span in the trace: nothing to read
    lost = dict(EVENTS, host=EVENTS["host"][1:])
    assert read[name](dict(obs, events=lost, trace=None)) is None


def test_tally_readers(read, obs, spans, tmp_path, monkeypatch):
    """A tally set through the program's own span() under a profiler
    session on the CPU."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.new_session()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for kind in ("narrow", "named_reduce", "narrow", "named_reduce"):
            with spans.span("launch", kind):
                pass
        for _ in range(2):
            with spans.span("fetch", nbytes=4):
                time.sleep(0.002)
        with spans.span("decode") as sp:
            sp.nbytes = 100
            time.sleep(0.002)
        with spans.span("pivot"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    tally = spans.session()
    assert read["launches_per_action"](obs) == 2
    assert read["host_syncs_per_action"](obs) == 1
    assert read["result_decode_s_per_action"](obs) \
        == tally["decode"]["seconds"] / 2 >= 0.001
    assert read["collect_pivot_s_per_action"](obs) \
        == tally["pivot"]["seconds"] / 2 >= 0.001
    spanned = sum(acc["seconds"] for acc in tally.values())
    assert 0.006 <= spanned < 1.0
    # spans + unspanned = the mean client wall, by construction
    assert read["unspanned_host_s_per_action"](obs) + spanned / 2 \
        == pytest.approx(4.5, abs=1e-12)
    tally_readers = ("launches_per_action", "host_syncs_per_action",
                     "result_decode_s_per_action",
                     "collect_pivot_s_per_action",
                     "unspanned_host_s_per_action")
    # no action completed: nothing to divide by
    for name in tally_readers:
        assert read[name](dict(obs, actions=0, action_walls=[])) is None
    # a session in which the program opened no span of these names
    monkeypatch.setattr(spans, "_session", {})
    for name in tally_readers:
        assert read[name](obs) is None

    monkeypatch.setattr(spans, "_programs", {
        "narrow": {"mints": 1, "first_call_s": 1.5},
        "join": {"mints": 2, "first_call_s": 2.0}})
    assert read["program_first_call_s"](obs) == 3.5
    monkeypatch.setattr(spans, "_programs", {})
    assert read["program_first_call_s"](obs) is None


def test_a_program_without_spans_gives_nothing(read, obs, spans, monkeypatch):
    """The parent commit has no vega_tpu/tpu/spans.py: the readers return
    None and do not raise."""
    import vega_tpu.tpu

    monkeypatch.delattr(vega_tpu.tpu, "spans")
    monkeypatch.setitem(sys.modules, "vega_tpu.tpu.spans", None)
    for name in ("launches_per_action", "host_syncs_per_action",
                 "result_decode_s_per_action", "collect_pivot_s_per_action",
                 "unspanned_host_s_per_action", "program_first_call_s"):
        assert read[name](obs) is None


def _new_metrics_of(manifest, cell):
    return [m["name"] for m in manifest["per_layer"]
            if m["name"] in NEW and cell in m["workloads"]]


BATCH = ["agg_join_64m.batch", "sort_64m.batch", "agg_join_256m_4chip.batch"]
SCAN = "agg_join_64m.scan"
# metric -> cells in whose traced run its reader finds something to read; a
# later PR may append cells to a list, or entries to the manifest
LISTED = {
    "result_fetch_s_per_action": BATCH,
    "result_decode_s_per_action": BATCH,
    "collect_pivot_s_per_action": ["agg_join_64m.batch",
                                   "agg_join_256m_4chip.batch"],
    "launches_per_action": BATCH + [SCAN],
    "host_syncs_per_action": BATCH + [SCAN],
    "unspanned_host_s_per_action": BATCH + [SCAN],
    "program_first_call_s": BATCH + [SCAN],
    "device_idle_unspanned_share": BATCH,
    "collective_s_per_action": ["agg_join_256m_4chip.batch"],
}


@pytest.mark.parametrize("name", NEW)
def test_manifest_lists(manifest, name):
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells["agg_join_256m_4chip.batch"]["chips"] == 4
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert not [c for c in LISTED[name] if c not in entry["workloads"]]
    assert not [c for c in entry["workloads"] if c not in cells]
    assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    # the profiler aligns the host's and the device's clocks to within
    # 0.4 ms, differently each session: an event reader of host spans
    # against device operations is noise at the scan cell's 3 ms action
    if name in ("result_fetch_s_per_action", "device_idle_unspanned_share"):
        assert SCAN not in entry["workloads"]


@pytest.mark.parametrize("cell", ["agg_join_64m.batch", "sort_64m.batch",
                                  "agg_join_64m.scan",
                                  "agg_join_256m_4chip.batch"])
def test_traced_rehearsal_prints_them(manifest, cell):
    """As test_rehearse.py starts it; the four-chip cell rehearses on four
    CPU devices."""
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "2147483711", "--seconds", "0.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(ln for ln in p.stderr.splitlines() if "] metrics: " in ln)
    values = json.loads(line.split("] metrics: ", 1)[1])
    expected = _new_metrics_of(manifest, cell)
    assert expected and not [n for n in expected if n not in values]
    for name in ("launches_per_action", "host_syncs_per_action"):
        assert values[name]["value"] == int(values[name]["value"]) >= 1
    if cell.endswith(".scan"):
        assert values["launches_per_action"]["value"] == 2
        assert values["host_syncs_per_action"]["value"] == 1
    if cell.startswith("agg_join_256m_4chip"):
        assert "devices: 4" in p.stderr
    assert "in-window mints 0 compiles 0" in p.stderr
