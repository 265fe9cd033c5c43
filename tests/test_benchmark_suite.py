"""The benchmark's own tests (benchmarks/tests/test_benchmark.py), brought
into the tier-1 run: the driver collects `tests/` only, and the data files
under benchmarks/ (a per-layer metric is one of them) are guarded where it
looks. The tests and their fixtures are the benchmark's, unchanged; this
file puts its directories on the path, as benchmarks/tests/conftest.py
does, and takes their names."""

import gc
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

pytest.register_assert_rewrite("test_benchmark")

from test_benchmark import *  # noqa: E402,F401,F403


@pytest.fixture(autouse=True)
def _no_full_collection_inside_a_window():
    """The traced tiny runs place their slice by the clock, 60 ms into a
    window of 150 ms. A full collection of a long-lived test worker's heap
    inside one request can outlast the rest of the window ("the window
    ended before the traced slice began"): what is alive now is set aside
    from the collector for the test."""
    gc.collect()
    gc.freeze()
    yield
    gc.unfreeze()
