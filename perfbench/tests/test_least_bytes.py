"""Each action's least-bytes function against a hand count at a toy size,
and the metric readers' arithmetic."""

import os

from conftest import BENCH, load


def test_agg_join_least_bytes():
    mod = load(os.path.join(BENCH, "configs", "agg_join_64m.py"))
    cfg = {"resident_row_bytes": 8}
    acts = mod.actions(cfg)
    size = {"rows": 1000, "keys": 10}
    # 1000 fact rows x 8 B + 10 table rows x 8 B read; 10 rows x 12 B written
    assert acts["reduce_join_collect"].least_bytes(size, cfg) == 8000 + 80 + 120
    # 1000 float32 values read, one int32 written
    assert acts["count_where"].least_bytes(size, cfg) == 4004
    assert acts["count_where"].rows_read(size) == 1000


def test_sort_least_bytes():
    mod = load(os.path.join(BENCH, "configs", "sort_64m.py"))
    cfg = {"resident_row_bytes": 12, "take": 5}
    act = mod.actions(cfg)["sort_collect_take"]
    # sort: 100 rows read + 100 written; take: 100 read + 5 written; an
    # 8-byte key and a 4-byte value each
    assert act.least_bytes({"rows": 100, "take": 5}, cfg) == (300 + 5) * 12


def test_readers():
    obs = {"actions": 4, "action_walls": [3.0, 1.0, 2.0, 10.0],
           "window": {"stages": 8}, "total": {"mints": 3},
           "feed": {"seconds": 0.5, "bytes": 1e9},
           "trace": {"busy_s": 2.0, "window_s": 8.0,
                     "ops": [["fusion.1 s32[8] fusion kCustom",
                              "gather-scatter fusion", 0.75, 4],
                             ["sort.2 s32[8] sort", "sort", 1.0, 4],
                             ["fusion.3 s32[8] fusion kCustom",
                              "gather-scatter fusion", 0.25, 4]]},
           "peak_hbm_bytes": 123,
           "peaks": {"hbm_bytes_per_s": 800e9}, "chips": 1,
           "least_bytes_per_action": 4e9}
    read = {n[:-3]: load(os.path.join(BENCH, "metrics", n)).read
            for n in os.listdir(os.path.join(BENCH, "metrics")) if n.endswith(".py")}
    assert read["dense_stages_per_action"](obs) == 2
    assert read["action_median_s"](obs) == 2.5
    assert read["action_median_s"](dict(obs, action_walls=[])) is None
    assert read["programs_minted"](obs) == 3
    assert read["feed_gbytes_per_s"](obs) == 2.0
    assert read["device_busy_s_per_action"](obs) == 0.5
    assert read["gather_scatter_s_per_action"](obs) == 0.25
    no_gathers = dict(obs, trace=dict(obs["trace"], ops=obs["trace"]["ops"][1:2]))
    assert read["gather_scatter_s_per_action"](no_gathers) is None
    assert read["device_idle_share"](obs) == 75.0
    assert read["peak_hbm_bytes"](obs) == 123
    # 4e9 B at 800e9 B/s is 5 ms; against 0.5 s busy: 1%
    assert abs(read["action_hbm_roofline"](obs) - 1.0) < 1e-12
    # nothing to read gives nothing, never 0
    empty = dict(obs, trace=None, window={"stages": 0}, peak_hbm_bytes=0)
    for name in ("dense_stages_per_action", "device_busy_s_per_action",
                 "gather_scatter_s_per_action", "action_hbm_roofline", "device_idle_share", "peak_hbm_bytes"):
        assert read[name](empty) is None
