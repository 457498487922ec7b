"""The comparison that decides `correct` is one that has been shown to fail:
each configuration's controls come out as not correct, and a run with the
timed path broken underneath prints correct false."""

import os

import numpy as np
import pytest

from conftest import BENCH, load


def _cfg(name):
    import json

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
@pytest.mark.parametrize("config,action", [
    ("agg_join_64m", "reduce_join_collect"), ("agg_join_64m", "count_where"),
    ("sort_64m", "sort_collect_take")])
def test_reference_passes_and_every_control_fails(config, action, seed):
    cfg = _cfg(config)
    mod = load(os.path.join(BENCH, "configs", config + ".py"))
    size = mod.sizes(cfg, 1, True)
    data = mod.make_data(seed, cfg, size)
    act = mod.actions(cfg)[action]
    ref = act.reference(data)
    assert all(v <= lim for v, lim in act.compare(ref, ref).values())
    controls = act.controls(data)
    assert controls
    for name, answer in controls.items():
        assert any(v > lim for v, lim in act.compare(answer, ref).values()), name


def test_same_seed_same_data():
    cfg = _cfg("sort_64m")
    mod = load(os.path.join(BENCH, "configs", "sort_64m.py"))
    size = mod.sizes(cfg, 1, True)
    a, b = mod.make_data(2**31 + 9, cfg, size), mod.make_data(2**31 + 9, cfg, size)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["keys"], mod.make_data(3, cfg, size)["keys"])


def _on_every_action(fault):
    """A fault planted in each action the configuration's module hands out."""
    def plant(mod):
        actions = mod.actions

        def broken_actions(cfg):
            acts = actions(cfg)
            for act in acts.values():
                fault(act)
            return acts

        mod.actions = broken_actions
    return plant


@_on_every_action
def _alter_answer(act):
    """A row of the answer altered where it is produced."""
    call = act.call

    def broken(nodes):
        result = call(nodes)
        if isinstance(result, list):  # rows of (k, (sum, table value))
            k, (lv, rv) = result[len(result) // 2]
            result[len(result) // 2] = (k, (lv + 1.0, rv))
        elif isinstance(result, dict):  # sorted columns + take
            result["arrays"]["v"] = result["arrays"]["v"].copy()
            result["arrays"]["v"][-1] += 1.0
        else:
            result += 1
        return result

    act.call = broken


def _half_the_rows(mod):
    """Half of the fact table left out of what the device holds."""
    feed = mod.feed

    def broken(ctx, data):
        half = len(data["keys"]) // 2
        return feed(ctx, dict(data, keys=data["keys"][:half],
                              vals=data["vals"][:half]))

    mod.feed = broken


@_on_every_action
def _mint_now_and_then(act):
    """Every seventh action is built on a closure of its own, so that the
    program cache cannot serve it: a program minted inside the window."""
    build, calls = act.build, [0]

    def broken(src):
        calls[0] += 1
        if calls[0] % 7:
            return build(src)
        n = calls[0]
        return {"flags": src["pairs"].map_values(
            lambda v, _n=n: (v >= 504).astype("int32") + 0 * _n).values_dense()}

    act.build = broken


def _break(run_mod, monkeypatch, fault):
    load_module = run_mod.load_module

    def loading(path):
        mod = load_module(path)
        if os.sep + "configs" + os.sep in path:
            fault(mod)
        return mod

    monkeypatch.setattr(run_mod, "load_module", loading)


@pytest.mark.parametrize("fault", [_alter_answer, _half_the_rows])
@pytest.mark.parametrize("cell", ["agg_join_64m.batch", "sort_64m.batch",
                                  "agg_join_64m.scan"])
def test_a_broken_timed_path_is_not_correct(run_mod, monkeypatch, capsys,
                                            cell, fault):
    _break(run_mod, monkeypatch, fault)
    rc = run_mod.main(["--workload", cell, "--seed", "11", "--seconds", "0.2",
                       "--trace", "0", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 5 and "correct False" in out.out
    assert "correct: False" in out.err


def test_a_mint_inside_the_window_is_not_correct(run_mod, monkeypatch, capsys):
    _break(run_mod, monkeypatch, _mint_now_and_then)
    rc = run_mod.main(["--workload", "agg_join_64m.scan", "--seed", "12",
                       "--seconds", "0.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 5 and "correct: False" in out.err
    assert "compared count_abs_err: 0 (limit 0)" in out.err
    mints = [ln for ln in out.err.splitlines() if "compared window_mints" in ln]
    assert mints and not mints[0].endswith(": 0 (limit 0)")


def test_only_a_closed_loop_of_one_client(run_mod, monkeypatch):
    load_json = run_mod.load_json
    monkeypatch.setattr(run_mod, "load_json", lambda path: dict(
        load_json(path), clients=4) if "workloads" in path else load_json(path))
    with pytest.raises(SystemExit, match="4 clients"):
        run_mod.main(["--workload", "agg_join_64m.scan", "--rehearse"])


def test_the_sound_path_is_correct(run_mod, capsys):
    rc = run_mod.main(["--workload", "agg_join_64m.batch", "--seed", str(2**31 + 3),
                       "--seconds", "0.2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 0 and "correct True" in out.out and "platform: cpu" in out.out
    assert '"metrics"' not in out.out  # a rehearsal prints no result line
