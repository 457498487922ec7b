"""Device seconds per action in the remap of string codes onto a merged
dictionary, the stage `vega.dict_remap` (dense_rdd._DictUnifyRDD: one gather
of each side's codes through its staged table). Self seconds of the traced
window's device operations whose compiled instruction carries that scope,
averaged over the chips, per completed action: perfbench/stage_ops.py joins
the profile's operations with the program's stage tables. Nothing where the
window ran no such operation or the program keeps no table."""

import importlib.util
import os
import sys

_NAME = "perfbench_stage_ops"


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
    return _stage_ops().seconds_per_action(obs, "dict_remap")
