"""Unit tests for the runtime lock-order sanitizer (utils/locksan.py):
inert when AMTPU_LOCKSAN is unset, records committed-order inversions
at level 1, raises at level 2, resolves renamed locks by manifest-name
prefix, depth-counts reentrant acquires, and flags long holds only
when another thread is actually blocked."""

import json
import threading
import time

import pytest

from automerge_tpu.utils import locksan

MANIFEST = {
    "version": 1,
    "locks": [{"id": "A._a", "name": "alpha"},
              {"id": "B._b", "name": "beta"}],
    "order": [{"before": "A._a", "after": "B._b", "site": "A.both"}],
    "lockfree": [],
}


@pytest.fixture(autouse=True)
def _sanitizer_isolation(monkeypatch):
    """Every test leaves the module exactly as an unconfigured process
    would see it: env restored first, then caches re-read."""
    yield
    monkeypatch.undo()
    locksan._reload_for_tests()


def _arm(monkeypatch, tmp_path, lvl, hold_s=None, manifest=MANIFEST):
    path = tmp_path / "locks_manifest.json"
    path.write_text(json.dumps(manifest))
    monkeypatch.setenv("AMTPU_LOCKSAN_MANIFEST", str(path))
    monkeypatch.setenv("AMTPU_LOCKSAN", str(lvl))
    if hold_s is not None:
        monkeypatch.setenv("AMTPU_LOCKSAN_HOLD_S", str(hold_s))
    locksan._reload_for_tests()


def test_inert_when_unset(monkeypatch):
    monkeypatch.delenv("AMTPU_LOCKSAN", raising=False)
    locksan._reload_for_tests()
    assert locksan.on is False and locksan.level() == 0
    # the factory hands out a plain Lock: zero wrapper overhead
    lock = locksan.named_lock("alpha")
    assert isinstance(lock, type(threading.Lock()))
    # the hooks are no-ops, not errors
    locksan.note_acquire("alpha")
    locksan.note_release("alpha")
    assert locksan.violations() == []


def test_committed_order_is_clean(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 1)
    a = locksan.named_lock("alpha")
    b = locksan.named_lock("beta")
    with a:
        with b:
            pass
    assert locksan.violations() == []


def test_inversion_recorded_at_level_one(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 1)
    a = locksan.named_lock("alpha")
    b = locksan.named_lock("beta")
    with b:
        with a:        # manifest commits alpha (A._a) before beta (B._b)
            pass
    vs = locksan.violations()
    assert [v["kind"] for v in vs] == ["order"]
    assert vs[0]["lock"] == "alpha" and vs[0]["held"] == "beta"
    assert "A._a -> B._b" in vs[0]["detail"]


def test_strict_mode_raises(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 2)
    a = locksan.named_lock("alpha")
    b = locksan.named_lock("beta")
    b.acquire()
    try:
        with pytest.raises(locksan.LockOrderViolation):
            a.acquire()
        # strict raises AFTER the acquire: the lock is held past the
        # raise (documented test/storm-harness caveat)
        a.release()
    finally:
        b.release()
    assert len(locksan.violations()) == 1


def test_prefix_rename_keeps_identity(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 1)
    a3 = locksan.named_lock("alpha_shard3")     # resolves to A._a
    b = locksan.named_lock("beta")
    with b:
        with a3:
            pass
    vs = locksan.violations()
    assert len(vs) == 1 and vs[0]["lock_id"] == "A._a"


def test_unknown_name_skips_order_checking(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 2)
    mystery = locksan.named_lock("unmapped")
    b = locksan.named_lock("beta")
    with b:
        with mystery:      # no manifest identity: nothing to invert
            pass
    assert locksan.violations() == []


def test_reentrant_acquire_depth_counts(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 2)
    # simulate an RLock wrapper reporting the same name twice
    locksan.note_acquire("alpha")
    locksan.note_acquire("alpha")
    locksan.note_release("alpha")
    locksan.note_release("alpha")
    assert locksan.violations() == []
    assert getattr(locksan._tls, "stack") == []


def test_long_hold_flagged_only_with_waiters(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 1, hold_s=0.01)
    lock = locksan.named_lock("alpha")

    # slow hold, nobody waiting: silent
    with lock:
        time.sleep(0.03)
    assert locksan.violations() == []

    # slow hold with a blocked thread: flagged
    lock.acquire()
    t = threading.Thread(target=lambda: (lock.acquire(), lock.release()))
    t.start()
    for _ in range(200):                     # wait for the thread to block
        with locksan._meta_lock:
            if locksan._waiters.get("alpha"):
                break
        time.sleep(0.005)
    time.sleep(0.03)
    lock.release()
    t.join(timeout=5)
    vs = [v for v in locksan.violations() if v["kind"] == "long-hold"]
    assert len(vs) == 1
    assert vs[0]["waiters"] >= 1 and vs[0]["hold_s"] >= 0.01


def test_long_hold_never_raises_in_strict(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 2, hold_s=0.0)
    lock = locksan.named_lock("alpha")
    lock.acquire()
    t = threading.Thread(target=lambda: (lock.acquire(), lock.release()))
    t.start()
    for _ in range(200):
        with locksan._meta_lock:
            if locksan._waiters.get("alpha"):
                break
        time.sleep(0.005)
    lock.release()                           # must NOT raise
    t.join(timeout=5)


def test_missing_manifest_disarms_order_checks(monkeypatch, tmp_path):
    monkeypatch.setenv("AMTPU_LOCKSAN_MANIFEST",
                       str(tmp_path / "absent.json"))
    monkeypatch.setenv("AMTPU_LOCKSAN", "2")
    locksan._reload_for_tests()
    a = locksan.named_lock("alpha")
    b = locksan.named_lock("beta")
    with b:
        with a:
            pass
    assert locksan.violations() == []


def test_reload_for_tests_resets_everything(monkeypatch, tmp_path):
    _arm(monkeypatch, tmp_path, 1)
    b = locksan.named_lock("beta")
    a = locksan.named_lock("alpha")
    with b:
        with a:
            pass
    assert locksan.violations()
    monkeypatch.delenv("AMTPU_LOCKSAN")
    locksan._reload_for_tests()
    assert locksan.on is False
    assert locksan.violations() == []


def test_violation_discloses_to_metrics_and_flightrec(monkeypatch,
                                                      tmp_path):
    """An order violation lands on all three disclosure surfaces with
    the right shapes: the labeled counter, a flightrec event whose kind
    stays `locksan_violation` (the violation class rides as
    `violation` — regression: it used to clobber the event kind), and
    the bounded list."""
    from automerge_tpu.utils import flightrec, metrics
    _arm(monkeypatch, tmp_path, 1)
    # the ring is bounded: where earlier tests of this worker have filled
    # it, "the events after the first `seen`" would be none
    flightrec.reset()
    with locksan.named_lock("beta"):
        with locksan.named_lock("alpha"):
            pass
    snap = metrics.snapshot()
    assert snap.get(
        "obs_locksan_order_violations_total{lock=alpha}", 0) >= 1
    ev = [e for e in flightrec.events()
          if e.get("kind") == "locksan_violation"]
    assert len(ev) == 1
    assert ev[0]["violation"] == "order" and ev[0]["lock"] == "alpha"


def test_arms_at_import_in_fresh_process(tmp_path):
    """AMTPU_LOCKSAN=1 must arm at import: the lockprof fast path tests
    `locksan.on` directly, so a process whose only named locks are
    lockprof wrappers never calls level() — the flag has to be correct
    without it (regression: it used to stay False until the first
    named_lock/level call)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, AMTPU_LOCKSAN="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from automerge_tpu.utils import locksan; print(locksan.on)"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "True"


def test_lockprof_reports_to_sanitizer(monkeypatch, tmp_path):
    """The instrumented-lock plane feeds the sanitizer: an inversion
    through lockprof wrappers is caught exactly like a named_lock one."""
    from automerge_tpu.utils import lockprof
    _arm(monkeypatch, tmp_path, 1)
    a = lockprof.InstrumentedLock("alpha")
    b = lockprof.InstrumentedLock("beta")
    with b:
        with a:
            pass
    vs = locksan.violations()
    assert [v["kind"] for v in vs] == ["order"]
    assert vs[0]["lock"] == "alpha"
