"""Regression pins for bench.py's driver-side hygiene: it suspends the
periodic faulthandler stack dumps around timed host-side measurement
regions and RE-ARMS them after — the dumps exist for hang forensics, not
to perturb single-core timings — and its parent fails, with a non-zero
exit code, where no chip answers and the CPU was not asked for.

bench.py is imported by file path: it keeps heavy imports deferred, so
importing the module is stdlib-cheap."""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(name, filename):
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(name,
                                                  str(ROOT / filename))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load("bench", "bench.py")


class _FHRecorder:
    """Stand-in for the faulthandler module surface bench uses."""

    def __init__(self):
        self.calls = []

    def dump_traceback_later(self, interval, repeat=False, exit=False,
                             file=None):
        self.calls.append(("arm", interval, repeat))

    def cancel_dump_traceback_later(self):
        self.calls.append(("cancel",))


# -- faulthandler hygiene around timed regions ------------------------------


def test_quiet_dumps_cancels_then_rearms(bench, monkeypatch):
    rec = _FHRecorder()
    monkeypatch.setitem(sys.modules, "faulthandler", rec)
    monkeypatch.setattr(bench, "_fh_armed", True)
    with bench._quiet_traceback_dumps():
        assert rec.calls == [("cancel",)], (
            "the periodic dump must be CANCELLED inside a timed region")
    assert rec.calls[-1] == ("arm", bench._FH_INTERVAL_S, True), (
        "the dump must re-arm (repeat=True) when the region exits")


def test_quiet_dumps_rearms_even_when_region_raises(bench, monkeypatch):
    rec = _FHRecorder()
    monkeypatch.setitem(sys.modules, "faulthandler", rec)
    monkeypatch.setattr(bench, "_fh_armed", True)
    with pytest.raises(RuntimeError):
        with bench._quiet_traceback_dumps():
            raise RuntimeError("timed region died")
    assert rec.calls[-1][0] == "arm", (
        "hang forensics must survive a failing measurement region")


def test_quiet_dumps_noop_when_never_armed(bench, monkeypatch):
    """Library/test use never arms the watchdog; the context manager
    must not arm it either (arming belongs to the bench worker only)."""
    rec = _FHRecorder()
    monkeypatch.setitem(sys.modules, "faulthandler", rec)
    monkeypatch.setattr(bench, "_fh_armed", False)
    with bench._quiet_traceback_dumps():
        pass
    assert rec.calls == []


def test_arm_sets_flag_and_uses_repeat(bench, monkeypatch):
    rec = _FHRecorder()
    monkeypatch.setitem(sys.modules, "faulthandler", rec)
    monkeypatch.setattr(bench, "_fh_armed", False)
    bench._arm_traceback_dumps()
    assert bench._fh_armed is True
    assert rec.calls == [("arm", bench._FH_INTERVAL_S, True)]


def test_timed_bench_regions_run_under_quiet_dumps():
    """Every timed host-side measurement helper must route through
    _quiet_traceback_dumps — a new timed region added without it brings
    the perturbation class back. Source-level pin (the helpers defer
    their timing to runtime, so a static check is the cheap reliable
    one)."""
    src = (ROOT / "bench.py").read_text()
    for fn in ("def run_oracle(", "def run_oracle_split(",
               "def run_doc_obs_config(", "def _fleet_health_subrun(",
               "def _fleet_health_overhead_ab("):
        body = src.split(fn, 1)[1].split("\ndef ", 1)[0]
        assert "_quiet_traceback_dumps()" in body, (
            f"{fn.strip('def (')} times host work without suspending "
            "the periodic faulthandler dumps")


# -- no chip, no result, no exit code 0 -------------------------------------


def test_parent_fails_without_a_chip_unless_cpu_was_asked_for():
    """Without an accelerator and without --force-cpu the canary worker
    refuses, the parent runs nothing on the CPU on its own, still prints
    its one JSON line — and exits non-zero, naming the configs that have
    no result."""
    import json
    import os
    import subprocess

    history = (ROOT / "bench_history.jsonl").read_bytes()
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench.py"), "--config", "1"],
        capture_output=True, text=True, timeout=300, cwd=str(ROOT),
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 AMTPU_BENCH_TIMEOUT="300"))
    assert out.returncode == 1, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["configs"] == {} and rec["configs_without_result"] == [1]
    assert rec["attempts"] == ["canary:3"], "no CPU sweep may follow"
    assert rec["errors"] >= 1
    assert (ROOT / "bench_history.jsonl").read_bytes() == history, (
        "a run that measured nothing must not enter the history")


def test_no_silent_cpu_pin_left_in_the_worker():
    """Source-level pin: the worker pins the CPU only under --force-cpu
    (the backend-init `except` that pinned it on its own is gone), and the
    parent's exit code depends on what was measured."""
    src = (ROOT / "bench.py").read_text()
    worker = src.split("def worker_main(", 1)[1].split("\ndef ", 1)[0]
    assert worker.count('jax.config.update("jax_platforms", "cpu")') == 1
    assert "if args.force_cpu:" in worker
    parent = src.split("def parent_main(", 1)[1].split("\ndef ", 1)[0]
    assert "sys.exit(1 if missing else 0)" in parent
    assert "sys.exit(0)" not in parent
