"""Observability: counters, structured spans, watchdog, exporters."""

import json
import logging
import threading
import time

import pytest

import automerge_tpu as am
from automerge_tpu import metrics


def test_counters_track_applied_changes():
    metrics.reset()
    s = am.change(am.init(), lambda d: d.__setitem__("a", 1))
    s = am.change(s, lambda d: am.assign(d, {"b": 2, "c": 3}))
    snap = metrics.snapshot()
    assert snap["core_changes_applied"] == 2
    assert snap["core_ops_applied"] == 3
    assert snap["core_diffs_emitted"] >= 3


def test_engine_counters():
    metrics.reset()
    from automerge_tpu.engine.batchdoc import apply_batch
    s = am.change(am.init("A"), lambda d: d.__setitem__("x", 1))
    apply_batch([s._doc.opset.get_missing_changes({})])
    snap = metrics.snapshot()
    assert snap["engine_docs_reconciled"] == 1
    assert snap["engine_ops_reconciled"] == 1
    assert snap["engine_reconcile_count"] == 1
    assert snap["engine_reconcile_s"] > 0


def test_trace_context_manager():
    metrics.reset()
    with metrics.trace("custom_phase"):
        pass
    snap = metrics.snapshot()
    assert snap["custom_phase_count"] == 1
    assert "custom_phase_s" in snap


def test_reset():
    from automerge_tpu.utils.gcpause import gc_paused
    metrics.reset()
    am.change(am.init(), lambda d: d.__setitem__("a", 1))
    with gc_paused():       # a collection in between would be counted
        metrics.reset()
        assert metrics.snapshot() == {}


# -- structured tracer ------------------------------------------------------


def test_trace_records_timing_on_exception():
    metrics.reset()
    with pytest.raises(ValueError):
        with metrics.trace("failing_phase"):
            raise ValueError("boom")
    snap = metrics.snapshot()
    assert snap["failing_phase_count"] == 1
    assert "failing_phase_s" in snap


def test_span_nesting_records_depth_and_parent():
    metrics.reset()
    with metrics.trace("outer"):
        with metrics.trace("inner"):
            stacks = metrics.span_stacks()
    spans = {s["name"]: s for s in metrics.recent_spans()}
    assert spans["inner"]["depth"] == 1
    assert spans["inner"]["parent"] == "outer"
    assert spans["outer"]["depth"] == 0
    assert spans["outer"]["parent"] is None
    # while both were active, the stack showed the nesting
    (stack,) = stacks.values()
    assert stack[0].startswith("outer(") and stack[1].startswith("inner(")


def test_labeled_counters_and_spans():
    metrics.reset()
    metrics.bump("engine_kernels_dispatched", kernel="apply_doc")
    metrics.bump("engine_kernels_dispatched", 2, kernel="apply_final")
    with metrics.trace("sync_round_flush", shard="3"):
        pass
    snap = metrics.snapshot()
    assert snap["engine_kernels_dispatched{kernel=apply_doc}"] == 1
    assert snap["engine_kernels_dispatched{kernel=apply_final}"] == 2
    assert snap["sync_round_flush{shard=3}_count"] == 1
    assert "sync_round_flush{shard=3}_s" in snap


def test_trace_budget_post_hoc_flag():
    metrics.reset()
    with metrics.trace("slow_span", budget_s=0.0001):
        time.sleep(0.01)
    snap = metrics.snapshot()
    assert snap["obs_budget_exceeded{name=slow_span}"] == 1


def test_watchdog_fires_with_span_stack_diagnosis(caplog):
    metrics.reset()
    with caplog.at_level(logging.WARNING, "automerge_tpu.metrics"):
        with metrics.watchdog("stuck_region", budget_s=0.05):
            with metrics.trace("rows_hashes"):
                time.sleep(0.3)
    snap = metrics.snapshot()
    assert snap["obs_watchdog_fired{name=stuck_region}"] == 1
    (event,) = metrics.watchdog_events()
    assert event["name"] == "stuck_region"
    # the diagnosis names the active span stack, watched region included
    (stack,) = event["spans"].values()
    assert stack[0].startswith("stuck_region(")
    assert stack[1].startswith("rows_hashes(")
    assert any("watchdog 'stuck_region'" in r.getMessage()
               and "rows_hashes(" in r.getMessage()
               for r in caplog.records)


def test_watchdog_quiet_inside_budget():
    metrics.reset()
    with metrics.watchdog("fast_region", budget_s=30.0):
        pass
    assert metrics.watchdog_events() == []
    assert "obs_watchdog_fired{name=fast_region}" not in metrics.snapshot()


# -- exporters --------------------------------------------------------------


def test_snapshot_roundtrips_through_json():
    metrics.reset()
    s = am.change(am.init(), lambda d: d.__setitem__("a", 1))
    am.merge(am.init("other"), s)
    metrics.bump("engine_kernels_dispatched", kernel="apply_doc")
    metrics.observe("sync_round_seconds", 0.25)
    with metrics.trace("outer"):
        pass
    snap = metrics.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_prometheus_exposition():
    metrics.reset()
    metrics.bump("sync_frames_received", 3)
    metrics.bump("engine_kernels_dispatched", kernel="apply_doc")
    metrics.gauge("core_queue_depth", 2)
    metrics.observe("sync_round_seconds", 0.5)
    with metrics.trace("engine_reconcile"):
        pass
    text = metrics.prometheus()
    assert "# TYPE amtpu_sync_frames_received counter" in text
    assert "amtpu_sync_frames_received 3" in text
    assert 'amtpu_engine_kernels_dispatched{kernel="apply_doc"} 1' in text
    assert "# TYPE amtpu_core_queue_depth gauge" in text
    assert "amtpu_sync_round_seconds_count 1" in text
    assert "amtpu_sync_round_seconds_sum 0.5" in text
    assert "amtpu_engine_reconcile_seconds_total" in text


def test_pre_rename_alias_names_are_gone():
    """The one-release alias window is over: snapshots carry canonical
    names only, and the alias table is empty (extension code probing
    metrics.ALIASES keeps working, it just finds nothing)."""
    metrics.reset()
    assert metrics.ALIASES == {}
    metrics.bump("sync_frames_received")
    snap = metrics.snapshot()
    assert snap["sync_frames_received"] == 1
    assert "wire_frames_received" not in snap


# -- thread safety ----------------------------------------------------------


def test_thread_safety_under_concurrent_bump_and_trace():
    metrics.reset()
    n_threads, n_iter = 8, 300
    barrier = threading.Barrier(n_threads)

    def worker(k):
        barrier.wait()
        for _ in range(n_iter):
            metrics.bump("core_changes_applied")
            metrics.bump("engine_kernels_dispatched", kernel=f"k{k % 2}")
            with metrics.trace("sync_round_flush", shard=str(k % 2)):
                metrics.observe("sync_round_seconds", 0.001)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = metrics.snapshot()
    total = n_threads * n_iter
    assert snap["core_changes_applied"] == total
    assert (snap["engine_kernels_dispatched{kernel=k0}"]
            + snap["engine_kernels_dispatched{kernel=k1}"]) == total
    assert (snap["sync_round_flush{shard=0}_count"]
            + snap["sync_round_flush{shard=1}_count"]) == total
    assert snap["sync_round_seconds_count"] == total
    assert not metrics.span_stacks()   # every span popped


def test_metrics_pull_message_roundtrip():
    """The METRICS message type: a peer pulls this node's snapshot over the
    ordinary Connection protocol."""
    from automerge_tpu.sync.connection import Connection
    from automerge_tpu.sync.docset import DocSet

    metrics.reset()
    metrics.bump("core_changes_applied", 7)
    a_out, b_out = [], []
    conn_a = Connection(DocSet(), a_out.append)
    conn_b = Connection(DocSet(), b_out.append)
    conn_a.request_metrics()
    (pull,) = a_out
    assert pull["metrics"] == "pull"
    assert "trace" in pull            # cross-replica trace context header
    conn_b.receive_msg(pull)          # serves its snapshot
    (resp,) = b_out
    assert resp["metrics"] == "snapshot"
    conn_a.receive_msg(resp)
    assert conn_a.peer_metrics["core_changes_applied"] == 7
    assert metrics.snapshot()["sync_metrics_pulls"] == 1
