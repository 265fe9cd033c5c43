"""What the phases leave of a served request is counted where it happens
(ISSUE 40): the collector under `gc`, on the thread that runs it and on the
profiler's clock; the rest of an outermost served span's self time under
`unnamed`; the serving thread's CPU time beside its wall time less its
declared waits; and the three per-layer metrics that read them, 0.0 on a
program that lacks them and a value in a tiny traced run of their cell."""

import gc
import json
import os
import shutil
import sys
import threading
import time

import pytest

import jax

from automerge_tpu.sync.service import request_span
from automerge_tpu.utils import metrics, perfscope

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from test_benchmark import (DEVICE_METRICS, TINY_FLEET,  # noqa: E402
                            TINY_MIX, _rewrite, eager)

CELL = "fleet10k-devices.storm"
NEW_METRICS = ("gc_share", "unnamed_share", "request_oncpu_share")


@pytest.fixture(autouse=True)
def _clean():
    metrics.reset()
    yield
    metrics.reset()


def phases() -> dict:
    return (metrics.snapshot().get("perf") or {}).get("phases") or {}


def counter(name: str) -> int:
    return metrics.snapshot().get(name, 0)


def spin(seconds: float) -> None:
    """Burn `seconds` of this thread's CPU time (a preempted thread
    spins longer on the wall clock, not on its own)."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# -- the collector ------------------------------------------------------------


def test_a_collection_inside_a_phase_counts_under_gc_nested_not_unnamed():
    junk = [[i] for i in range(20_000)]     # something to walk
    gc.collect()
    metrics.reset()
    with request_span(None):
        with perfscope.phase("encode"):
            gc.collect()
    del junk
    got = phases()
    assert got["gc"]["count"] >= 1 and got["gc"]["s"] > 0
    # nested: the phase's time holds the collection's
    assert got["encode"]["s"] >= got["gc"]["s"]
    # and the request's self time does not
    assert got["unnamed"]["count"] == 1
    assert got["unnamed"]["s"] < got["gc"]["s"]
    snap = metrics.snapshot()
    assert snap["obs_gc_collections{generation=2}"] >= 1
    assert "obs_gc_collections" in metrics.COUNTERS
    assert {"gc", "unnamed"} <= set(perfscope.PHASES)


def test_a_collection_outside_any_phase_is_the_collectors_not_unnamed():
    junk = [[i] for i in range(200_000)]    # a collection worth timing
    gc.collect()
    metrics.reset()
    with request_span(None):
        t0 = time.perf_counter()
        time.sleep(0.002)
        slept = time.perf_counter() - t0
        gc.collect()
    del junk
    got = phases()
    # the sleep is unnamed, the collection is not
    assert got["gc"]["s"] > 0
    assert slept <= got["unnamed"]["s"] < slept + 0.5 * got["gc"]["s"]


def test_the_collector_is_counted_on_the_thread_that_runs_it():
    def work():
        gc.collect(0)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    # the thread has exited: its slot is folded into the retired totals
    assert perfscope.gc_collections()[0] >= 1
    assert phases()["gc"]["count"] >= 1


def test_a_collection_holds_a_profiler_annotation_nested_in_its_phase(
        tmp_path):
    import tracefile

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with perfscope.phase("readback"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    data = tracefile.from_xplane(tracefile.newest_xplane(str(tmp_path)))
    events = [ev[:3] for pl in data["planes"] if pl["name"].startswith(
        "/host:") for ln in pl["lines"] for ev in ln["events"]]
    outer = [ev for ev in events if ev[0] == "readback"]
    inner = [ev for ev in events if ev[0] == "gc"]
    assert len(outer) == 1 and inner
    _n, s, d = outer[0]
    assert any(s <= gs and gs + gd <= s + d for _g, gs, gd in inner)


# -- what the phases leave of a served span ---------------------------------


def test_a_sleep_outside_any_phase_inside_a_request_is_unnamed():
    with request_span(None):
        with perfscope.phase("encode"):
            time.sleep(0.005)
        time.sleep(0.02)
    got = phases()["unnamed"]
    assert got["count"] == 1
    assert 0.02 <= got["s"] < 0.03


def test_a_request_made_of_phases_leaves_almost_nothing_unnamed():
    with request_span(None):
        for name in ("encode", "commit", "route", "publish"):
            with perfscope.phase(name):
                time.sleep(0.005)
    got = phases()
    assert got["unnamed"]["count"] == 1
    assert got["unnamed"]["s"] < 0.001, got


def test_only_the_outermost_served_span_counts():
    """A flush inside a request (a batch's exit, a locked ingest) is not a
    second served span; a served span opened inside a phase is none."""
    with perfscope.served():
        with perfscope.served():
            time.sleep(0.003)
        with perfscope.phase("publish"):
            with perfscope.served():
                time.sleep(0.003)
    got = phases()
    assert got["unnamed"]["count"] == 1
    assert 0.003 <= got["unnamed"]["s"] < 0.006
    assert counter("sync_serve_busy_us") >= 6000


def test_a_storm_round_leaves_little_unnamed():
    """A batch of 400 single-op changes on the rows service: the body is
    one `admit`, the flush its phases; what is left is the glue."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    from automerge_tpu.sync.service import EngineDocSet

    svc = EngineDocSet(backend="rows")
    try:
        eager(svc)
        for seq in (1, 2, 3):
            metrics.reset()
            with svc.batch():
                for d in range(400):
                    svc.apply_changes(f"d{d}", [Change(
                        "w", seq, {}, [Op("set", ROOT_ID, key="n",
                                         value=d)])])
        got = phases()
        assert got["admit"]["count"] == 1
        assert got["unnamed"]["count"] == 1
        flush = metrics.snapshot()["sync_request_s"]
        assert got["unnamed"]["s"] < 0.1 * flush, (got, flush)
    finally:
        svc.close()


# -- the serving thread's CPU time ------------------------------------------


def oncpu() -> float:
    return 100.0 * counter("sync_serve_cpu_us") / counter(
        "sync_serve_busy_us")


def test_a_sleeping_request_reads_off_the_cpu():
    with request_span(None):
        time.sleep(0.05)
    assert counter("sync_serve_busy_us") >= 50_000
    assert oncpu() < 20


def test_a_busy_request_reads_on_the_cpu():
    with request_span(None):
        spin(0.05)
    assert counter("sync_serve_cpu_us") >= 50_000
    # near 100 % on an idle host; beside five other test workers the
    # thread is preempted now and then, which only ever lowers it
    assert oncpu() > 50
    assert counter("sync_serve_preempted") >= 0


def test_a_declared_wait_is_not_busy():
    t0 = time.perf_counter()
    with request_span(None):
        w0 = time.perf_counter()
        with perfscope.phase("commit_wait"):
            time.sleep(0.03)
        waited = time.perf_counter() - w0
        spin(0.01)
    wall = time.perf_counter() - t0
    busy = counter("sync_serve_busy_us") / 1e6
    assert wall - waited - 0.002 <= busy <= wall - waited + 0.0005
    assert counter("sync_serve_cpu_us") >= 10_000


# -- the three per-layer metrics ----------------------------------------------


def metric_file(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_zero_on_a_program_without_its_counters(name):
    m = metric_file(name)
    assert m["workloads"] == [CELL]
    reader = run.load_by_path("readers", m["reader"])
    assert m["reader"].endswith("_since")
    older = {"window_s": 2.0, "delta": {"phase.encode": 0.5,
                                        "sync_request_count": 9}}
    assert reader.read(m["args"], older) == 0.0
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
              encoding="utf-8") as f:
        entry = next(e for e in json.load(f)["per_layer"]
                     if e["name"] == name)
    assert {k: entry[k] for k in entry} == {k: m[k] for k in entry}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny traced run of the cell the three metrics list."""
    from automerge_tpu.engine import dispatch

    root = str(tmp_path_factory.mktemp("serving") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name in os.listdir(os.path.join(root, "configs")):
        _rewrite(os.path.join(root, "configs", name), fleet=TINY_FLEET)
    _rewrite(os.path.join(root, "traffic", "storm.json"), **TINY_MIX["storm"])
    keys = ("dispatch_fixed_s", "h2d_call_s", "d2h_call_s")
    saved = {k: dispatch._LINK[k] for k in keys}
    dispatch.calibrate(dispatch_fixed_s=1e-5, h2d_call_s=1e-6,
                       d2h_call_s=1e-5)
    trace_dir, run.TRACE_DIR = run.TRACE_DIR, os.path.join(root, ".trace")
    try:
        res = run.run_cell(CELL, 2**31 + 40, 0.3, 1, jax.devices(),
                           root=root, steer=eager, max_requests=10_000,
                           may_miss=DEVICE_METRICS)
    finally:
        run.TRACE_DIR = trace_dir
        dispatch.calibrate(**saved)
    assert res["correct"] is True
    return {k: v["value"] for k, v in res["metrics"].items()}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_a_value_in_a_tiny_traced_run(traced, name):
    value = traced[name]
    if name == "gc_share":
        assert 0.0 <= value < 100.0
    elif name == "unnamed_share":
        assert 0.0 < value < 10.0
    else:
        # above 100 % here: on the CPU backend the declared waits run the
        # computation on the serving thread, so its CPU time counts what
        # the wall time less the waits leaves out
        assert value > 0.0
