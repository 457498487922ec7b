"""The dictionary remap's share of the HBM roofline: the least time the chip
could take to move the bytes a remap must move (`least_bytes`: each remapped
row's code read once and written once, and each staged table's entries read
once, from the program's counters `dict_remap_rows` and
`dict_remap_table_entries` under the traced window's session) at the chip's
peak bandwidth (peaks.json), over the device seconds of the stage
`vega.dict_remap` (perfbench/stage_ops.py), per action. The bytes are a
lower bound (the rows past a shard's count and a table's padding are not
counted), so the share cannot pass 100%. Nothing to read gives nothing,
never 0."""

import importlib.util
import os
import sys

_NAME = "perfbench_stage_ops"


def least_bytes(rows: int, table_entries: int) -> int:
    """4-byte codes: each read and written once; each table entry read
    once."""
    return 8 * rows + 4 * table_entries


def _stage_ops():
    """perfbench/stage_ops.py, loaded by path once a process."""
    if _NAME not in sys.modules:
        spec = importlib.util.spec_from_file_location(_NAME, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "stage_ops.py"))
        sys.modules[_NAME] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[_NAME])
    return sys.modules[_NAME]


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    tally = spans.session()
    rows = tally.get("dict_remap_rows")
    entries = tally.get("dict_remap_table_entries")
    if not rows or not entries or not obs["actions"] or not obs["peaks"]:
        return None
    seconds = _stage_ops().seconds_per_action(obs, "dict_remap")
    if not seconds:
        return None
    per_action = least_bytes(rows["count"], entries["count"]) \
        / obs["actions"] / obs["chips"]
    return 100.0 * per_action / obs["peaks"]["hbm_bytes_per_s"] / seconds
