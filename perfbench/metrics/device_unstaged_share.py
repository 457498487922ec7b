"""The part of the traced window's device self seconds that no stage claims:
operations that no minted program's stage table has, whose instruction carries
no `vega.` scope, or whose key two programs give different stages
(perfbench/stage_ops.py), over all self seconds of the window, x 100. It is
the stage tables' own gauge: kernel code without a scope, a join that stopped
matching, or a table read off another tree's executable shows here. Nothing
where the program keeps no table."""

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
    return _stage_ops().unstaged_share(obs)
