"""sort_256m_4chip — sort_64m's data, source, action, reference, control and
comparison, on the rows of four chips: the two configurations differ in
their .json alone (`sizes()` multiplies the per-chip rows by the cell's
chips), so this file takes every function from sort_64m.py, which it loads
by path as the harness loads a configuration."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "perfbench_configs_sort_64m_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "sort_64m.py"))
_one_chip = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_one_chip)

sizes = _one_chip.sizes
make_data = _one_chip.make_data
feed = _one_chip.feed
fed_bytes = _one_chip.fed_bytes
actions = _one_chip.actions
