"""The trace plane (utils/tracer.py): deterministic coordination-free
sampling, the inert-unset contract, the full local lifecycle through a
real EngineDocSet, wire-header stitching (manual roundtrip and over the
in-memory connection pair), bounded tables with disclosed truncation,
TTL expiry, section purity, and the metrics reset hook.
"""

import json
import string
import time

import pytest

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.native.wire import changes_to_columns
from automerge_tpu.sync.connection import Connection
from automerge_tpu.sync.frames import TRACEPLANE_KEY
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import flightrec, metrics, tracer

TRACE_VARS = ("AMTPU_TRACE_SAMPLE", "AMTPU_TRACE_RING")


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    """Every test starts and ends with the plane unset and empty."""
    for var in TRACE_VARS:
        monkeypatch.delenv(var, raising=False)
    tracer._reload_for_tests()
    tracer.reset()
    metrics.reset()          # runs the registered reset hook too
    flightrec.reset()
    yield
    for var in TRACE_VARS:
        monkeypatch.delenv(var, raising=False)
    tracer._reload_for_tests()
    tracer.reset()
    metrics.reset()
    flightrec.reset()


def _cols(actor, seq, key, value):
    return changes_to_columns([Change(
        actor=actor, seq=seq, deps={},
        ops=[Op("set", ROOT_ID, key=key, value=value)])])


def _complete_one(actor, seq, doc="d"):
    """Drive one trace through the module API to completion (origin-
    local path: finalize -> admit -> flush -> visible)."""
    tr = tracer.finalize_begin(actor, seq)
    tracer.finalize_end(tr)
    tracer.admit(doc)
    tracer.flush_round([doc], 1, time.perf_counter(), 0.0)
    tracer.visible([doc])


# ---------------------------------------------------------------------------
# sampling


def test_sampling_deterministic_and_coordination_free():
    tracer.set_sample_rate(4)
    first = [tracer.sampled("W", s) for s in range(64)]
    assert first == [tracer.sampled("W", s) for s in range(64)]
    assert any(first) and not all(first)
    # rate 1 samples everything
    tracer.set_sample_rate(1)
    assert all(tracer.sampled(a, s) for a in "ABC" for s in range(8))


def test_rate_parsing(monkeypatch):
    assert tracer.sample_rate() is None          # unset = off
    for bad in ("0", "-3", "garbage", ""):
        monkeypatch.setenv("AMTPU_TRACE_SAMPLE", bad)
        tracer._reload_for_tests()
        assert tracer.sample_rate() is None, bad
    monkeypatch.setenv("AMTPU_TRACE_SAMPLE", "8")
    tracer._reload_for_tests()
    assert tracer.sample_rate() == 8
    assert tracer.enabled()


# ---------------------------------------------------------------------------
# inert-unset contract


def test_unset_plane_records_nothing():
    assert tracer.finalize_begin("A", 1) is None
    tracer.finalize_end(None)
    tracer.origin_ingress([("A", 1)])
    tracer.admit("d")
    tracer.sealed(["d"])
    tracer.flush_round(["d"], 1, time.perf_counter(), 0.0)
    assert tracer.wire_header("d") is None
    tracer.visible()
    sec = tracer.section()
    assert sec["sample_rate"] is None
    assert sec["sampled"] == sec["completed"] == sec["inflight"] == 0
    assert sec["stages"] == {}


def test_unset_wire_envelope_carries_no_trace_key():
    ea, eb = EngineDocSet(backend="rows"), EngineDocSet(backend="rows")
    seen = []
    qa, qb = [], []
    ca = Connection(ea, lambda m: (seen.append(m), qa.append(m)),
                    wire="columnar")
    cb = Connection(eb, qb.append, wire="columnar")
    ca.open()
    cb.open()
    ea.apply_columns("doc1", _cols("A", 1, "x", 1))
    for _ in range(30):
        moved = False
        while qa:
            cb.receive_msg(qa.pop(0))
            moved = True
        while qb:
            ca.receive_msg(qb.pop(0))
            moved = True
        if not moved:
            break
    assert eb.hashes()["doc1"] == ea.hashes()["doc1"]
    assert seen and all(TRACEPLANE_KEY not in m for m in seen)


# ---------------------------------------------------------------------------
# the local lifecycle through a real service


def test_engine_service_origin_lifecycle_completes():
    tracer.set_sample_rate(1)
    svc = EngineDocSet(backend="rows")
    svc.apply_columns("d", _cols("A", 1, "x", 1))
    svc.hashes()                         # the converged-hash visibility read
    sec = tracer.section()
    assert sec["sampled"] == 1
    assert sec["completed"] == 1 and sec["stitched"] == 0
    (t,) = sec["exemplars"]
    assert t["role"] == "origin" and t["doc"] == "d"
    stages = [s[0] for s in t["spans"]]
    for st in ("finalize", "queue_wait", "coalesce_wait", "dispatch",
               "visibility"):
        assert st in stages, (st, stages)
    # spans tile: each rel start is >= the previous span's start
    rels = [s[1] for s in t["spans"]]
    assert rels == sorted(rels)
    assert t["crit_s"] >= 0.0


def test_unsampled_siblings_record_nothing():
    tracer.set_sample_rate(2)
    hot = next(a for a in string.ascii_uppercase if tracer.sampled(a, 1))
    cold = next(a for a in string.ascii_uppercase
                if not tracer.sampled(a, 1))
    svc = EngineDocSet(backend="rows")
    svc.apply_columns("d", _cols(hot, 1, "x", 1))
    svc.apply_columns("d", _cols(cold, 1, "y", 2))
    svc.hashes()
    sec = tracer.section()
    assert sec["sampled"] == 1 and sec["completed"] == 1
    assert all(t["actor"] == hot for t in sec["exemplars"])


def test_origin_ingress_dedups_frontend_finalized_trace():
    tracer.set_sample_rate(1)
    tr = tracer.finalize_begin("A", 1)
    tracer.finalize_end(tr)
    # the service boundary sees the same change again: no double-count
    tracer.origin_ingress([("A", 1), ("B", 1)])
    assert tracer.section()["sampled"] == 2      # A.1 once + B.1


def test_remote_apply_suppresses_origination():
    tracer.set_sample_rate(1)
    with tracer._plane.remote_apply():
        tracer.origin_ingress([("A", 1)])
    assert tracer.section()["sampled"] == 0
    tracer.origin_ingress([("A", 1)])            # outside: originates
    assert tracer.section()["sampled"] == 1


# ---------------------------------------------------------------------------
# stitching


def test_wire_header_roundtrip_stitches_one_trace():
    tracer.set_sample_rate(1)
    tr = tracer.finalize_begin("A", 7)
    tracer.finalize_end(tr)
    tracer.admit("d")
    tracer.flush_round(["d"], 3, time.perf_counter(), 0.001)
    hdr = tracer.wire_header("d", serialize_s=0.0005)
    assert hdr and hdr[0]["tid"] == "A.7"
    # the header is what rides the envelope: JSON-able end to end
    hdr = json.loads(json.dumps(hdr))
    adopted = tracer.wire_receive(hdr, "d")
    tracer.remote_admitted(adopted, "d", decode_s=0.0002,
                           admission_s=0.0004)
    tracer.visible(["d"])
    sec = tracer.section()
    assert sec["handed_off"] == 1 and sec["received"] == 1
    assert sec["completed"] == 1 and sec["stitched"] == 1
    (t,) = sec["exemplars"]
    assert t["stitched"] and t["role"] == "stitched"
    stages = [s[0] for s in t["spans"]]
    for st in ("finalize", "queue_wait", "dispatch", "wire_serialize",
               "wire", "remote_decode", "remote_admission", "visibility"):
        assert st in stages, (st, stages)
    # the flush round's metadata rode along
    assert t["meta"].get("round") is not None


def test_receiver_completes_even_when_locally_unset():
    """The sender paid the sampling decision: a receiver with
    AMTPU_TRACE_SAMPLE unset still adopts and completes the trace."""
    tracer.set_sample_rate(1)
    tr = tracer.finalize_begin("A", 1)
    tracer.finalize_end(tr)
    tracer.admit("d")
    tracer.flush_round(["d"], 1, time.perf_counter(), 0.0)
    hdr = tracer.wire_header("d")
    tracer.set_sample_rate(None)                 # the receiving side
    adopted = tracer.wire_receive(hdr, "d")
    assert adopted
    tracer.remote_admitted(adopted, "d")
    tracer.visible(["d"])
    sec = tracer.section()
    assert sec["completed"] == 1 and sec["stitched"] == 1


def test_malformed_wire_header_never_breaks_apply():
    tracer.set_sample_rate(1)
    assert tracer.wire_receive(None) is None
    assert tracer.wire_receive([]) is None
    assert tracer.wire_receive([{"actor": "A"}]) is None    # no seq/t0
    assert tracer.wire_receive("garbage") is None
    tracer.remote_admitted(None, "d")            # no-op, no raise


def test_wire_header_caps_per_doc_traces_with_disclosure():
    tracer.set_sample_rate(1)
    for seq in range(1, 7):
        tr = tracer.finalize_begin("A", seq)
        tracer.finalize_end(tr)
        tracer.admit("d")
    tracer.flush_round(["d"], 1, time.perf_counter(), 0.0)
    hdr = tracer.wire_header("d")
    assert len(hdr) == tracer.HEADER_MAX
    assert tracer.section()["dropped"] == 6 - tracer.HEADER_MAX


# ---------------------------------------------------------------------------
# bounded memory: ring, TTL, pending handoff


def test_completed_ring_bounded_with_disclosed_truncation(monkeypatch):
    monkeypatch.setenv("AMTPU_TRACE_RING", "8")
    tracer.reset()                               # re-reads the ring cap
    tracer.set_sample_rate(1)
    for seq in range(1, 13):
        _complete_one("A", seq)
    sec = tracer.section()
    assert sec["completed"] == 12
    assert sec["ring"] == sec["ring_cap"] == 8
    assert sec["truncated"] is True


def test_ttl_expiry_counts_instead_of_leaking():
    tracer.set_sample_rate(1)
    tr = tracer.finalize_begin("A", 1)
    tracer.finalize_end(tr)
    tracer.admit("d")
    tracer.flush_round(["d"], 1, time.perf_counter(), 0.0)
    with tracer._plane._lock:
        for traces in tracer._plane._awaiting_wire.values():
            for t in traces:
                t.born -= tracer.TTL_S + 1.0
    tracer.visible([])                           # expiry sweep, no doc
    sec = tracer.section()
    assert sec["expired"] == 1
    assert sec["inflight"] == 0 and sec["completed"] == 0


def test_pending_handoff_bounded():
    tracer.set_sample_rate(1)
    for seq in range(1, tracer.PENDING_MAX + 4):
        tr = tracer.finalize_begin("A", seq)
        tracer.finalize_end(tr)
    assert tracer.section()["dropped"] == 3      # oldest unclaimed out
    tracer.admit("d")                            # claims the survivors
    assert tracer.section()["inflight"] == tracer.PENDING_MAX


# ---------------------------------------------------------------------------
# export contract


def test_section_is_pure_and_json_able():
    tracer.set_sample_rate(1)
    _complete_one("A", 1)
    a = tracer.section()
    b = tracer.section()
    assert a == b                                # no read-side mutation
    json.dumps(a)                                # JSON-able throughout
    assert a["label"]
    assert list(a["stages"]) == [st for st in tracer.STAGES
                                 if st in a["stages"]]
    assert a["critical_path"]["count"] == 1
    snap = metrics.snapshot()
    assert snap["traceplane"]["nodes"][a["label"]]["completed"] == 1


def test_completion_emits_flightrec_exemplar():
    tracer.set_sample_rate(1)
    _complete_one("A", 1)
    kinds = [e["kind"] for e in flightrec.events()]
    assert "trace_exemplar" in kinds


def test_self_seconds_accounted():
    tracer.set_sample_rate(1)
    _complete_one("A", 1)
    assert tracer.self_seconds() > 0.0
    assert tracer.section()["self_s"] > 0.0


def test_inflight_snapshot_for_post_mortem():
    tracer.set_sample_rate(1)
    tr = tracer.finalize_begin("A", 1)
    tracer.finalize_end(tr)
    tracer.admit("d")
    live = tracer.inflight_snapshot()
    assert live and live[0]["tid"] == "A.1"
    assert live[0]["awaiting"] == "flush"


# ---------------------------------------------------------------------------
# the cross-process stitch over real TCP (the ISSUE acceptance path)


def test_tcp_stitch_one_trace_covers_both_processes():
    """A sampled change on node A crosses a REAL loopback socket and
    completes as ONE stitched trace whose spans cover both processes;
    its stage sum reconciles with the measured end-to-end lag; the
    unsampled sibling writes record nothing."""
    import numpy as np

    from automerge_tpu.sync.tcp import TcpSyncClient, TcpSyncServer

    tracer.set_sample_rate(2)
    hot = next(a for a in string.ascii_uppercase if tracer.sampled(a, 1))
    cold = next(a for a in string.ascii_uppercase
                if not tracer.sampled(a, 1))
    a = EngineDocSet(backend="rows")
    b = EngineDocSet(backend="rows")
    server = TcpSyncServer(a).start()
    client = TcpSyncClient(b, server.host, server.port).start()
    try:
        # warm the converged-hash path so the JIT compile does not land
        # inside the measured trace — on doc1 itself, with an unsampled
        # write: a document the peer already holds is framed by the
        # writer's own gossip, so the hot write's trace is handed to the
        # wire before apply returns. (A document new to the peer needs an
        # advert round trip first, and this thread's hash poll below could
        # complete the trace locally before the frame left.)
        a.apply_columns("doc1", _cols(cold, 1, "w", 0))
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            ha, hb = a.hashes(), b.hashes()
            if "doc1" in ha and "doc1" in hb:
                break
            time.sleep(0.02)

        t0 = time.perf_counter()
        a.apply_columns("doc1", _cols(hot, 1, "x", 1))
        a.apply_columns("doc1", _cols(cold, 2, "y", 2))
        converged = False
        e2e = None
        while time.perf_counter() < deadline:
            ha, hb = a.hashes(), b.hashes()
            if ("doc1" in ha and "doc1" in hb
                    and np.uint32(ha["doc1"]) == np.uint32(hb["doc1"])):
                e2e = time.perf_counter() - t0
                converged = True
                break
            time.sleep(0.02)
        assert converged, (a.hashes(), b.hashes())

        # the wire receive thread may still be parking the trace when the
        # hash loop exits — give completion a generous window (the flush
        # governor and socket scheduling can stretch this past a second)
        sec = tracer.section()
        for _ in range(500):
            if sec["inflight"] == 0 and sec["stitched"] >= 1:
                break
            time.sleep(0.02)
            a.hashes()
            b.hashes()
            sec = tracer.section()

        assert sec["sampled"] == 1          # hot write only; cold silent
        assert sec["handed_off"] >= 1 and sec["received"] >= 1
        assert sec["stitched"] >= 1, sec
        t = next(t for t in sec["exemplars"]
                 if t["stitched"] and t["doc"] == "doc1")
        assert t["actor"] == hot
        stages = [s[0] for s in t["spans"]]
        for st in ("finalize", "dispatch", "wire", "remote_admission",
                   "visibility"):
            assert st in stages, (st, stages)
        # stage sum reconciles with the trace's own critical path, and
        # that critical path reconciles with the measured e2e lag (the
        # poll interval and scheduling jitter bound the tolerance; at
        # millisecond-scale critical paths a few ms of scheduler gap can
        # exceed any relative bound, so the slack has an absolute floor)
        covered = sum(s[2] for s in t["spans"])
        uncovered = t["crit_s"] - covered
        assert uncovered <= max(0.25 * t["crit_s"], 0.05), (covered, t["crit_s"])
        assert t["crit_s"] <= e2e + 0.25, (t["crit_s"], e2e)
    finally:
        client.close()
        server.close()
        a.close()
        b.close()


def test_metrics_reset_hook_clears_plane():
    tracer.set_sample_rate(1)
    _complete_one("A", 1)
    assert tracer.section()["completed"] == 1
    metrics.reset()
    sec = tracer.section()
    assert sec["sampled"] == 0 and sec["completed"] == 0
    assert sec["ring"] == 0
