"""The benchmark's own tests (``benchmark/tests``) in tier 1.

They hold the program to what the benchmark takes from it: the bfloat16 and
broken-push controls that make ``correct`` fail, the window arithmetic, the
manifest, the scope readers against ``spmd.op_scopes()``. The driver runs
``pytest tests/``, so their test functions are brought into this module,
each under its own name. One file on purpose: ``--dist loadfile`` then gives
them one worker, and ``tiny.tiny_ctx`` points every run of a cell at the one
directory ``.bench_work/tiny.<cell>``.
"""

import glob
import importlib
import os
import sys

import pytest

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests")
if _DIR not in sys.path:
    sys.path.insert(0, _DIR)  # they import ``tiny`` and ``control`` by their bare names

for _path in sorted(glob.glob(os.path.join(_DIR, "test_*.py"))):
    _module = os.path.basename(_path)[:-3]
    pytest.register_assert_rewrite(_module)
    for _name, _obj in vars(importlib.import_module(_module)).items():
        if _name.startswith("test_"):
            assert _name not in globals(), f"{_module}.{_name} would shadow a test of another module"
            globals()[_name] = _obj
