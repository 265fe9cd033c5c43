"""Per-doc convergence ledger (sync/docledger.py): frontier lanes,
usefulness/duplicate accounting, bounded memory, pure-state export, and
the connection/service/tcp hooks that feed it."""

import json
import os
import time

import pytest

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.sync import docledger
from automerge_tpu.sync.connection import Connection
from automerge_tpu.sync.docledger import DocLedger
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _clean():
    metrics.reset()
    yield
    metrics.reset()


def _chg(actor, seq, value=1):
    return Change(actor=actor, seq=seq, deps={},
                  ops=[Op("set", ROOT_ID, key="k", value=value)])


def _pair(wire="columnar"):
    """Two rows services synced over in-process queue connections, with
    labeled lanes (the cross-node join perf explain needs)."""
    a, b = EngineDocSet(backend="rows"), EngineDocSet(backend="rows")
    qa, qb = [], []
    ca = Connection(a, qa.append, wire=wire)
    cb = Connection(b, qb.append, wire=wire)
    ca.peer_label, cb.peer_label = "B", "A"
    a.doc_ledger.label, b.doc_ledger.label = "A", "B"
    ca.open()
    cb.open()

    def drain():
        for _ in range(50):
            if not (qa or qb):
                return
            while qa:
                cb.receive_msg(qa.pop(0))
            while qb:
                ca.receive_msg(qb.pop(0))
        raise AssertionError("pair failed to quiesce")
    return a, b, ca, cb, drain


def _close(*svcs):
    for s in svcs:
        s.close()


# -- core lane mechanics ----------------------------------------------------


def test_advert_vs_local_frontier_builds_lag_then_clears():
    led = DocLedger(label="n")

    class _Conn:
        peer_label = "W"
    conn = _Conn()
    led.record_advert("d", conn, {"x": 3})
    sec = led.section()
    e = sec["docs"]["d"]
    # no doc_set attached: local frontier indeterminate -> no deficit
    # invented (lag stays 0 rather than lying)
    assert e["lag_changes"] == 0

    svc = EngineDocSet(backend="rows")
    try:
        led2 = svc.doc_ledger
        led2.label = "n2"
        led2.record_advert("d", conn, {"x": 3})
        e = led2.section()["docs"]["d"]
        # the service does NOT hold doc "d" at all: frontier {} by
        # definition, the whole advert is deficit
        assert e["lag_changes"] == 3
        assert e["behind_peer"] == "W"
        assert e["behind_since"] is not None
        # catch up: admit the changes, then the export-time catchup
        # (post-read cache warm) must clear the deficit
        for s in (1, 2, 3):
            svc.apply_changes("d", [_chg("x", s)])
        svc.clock_of("d")               # warm the snapshot read cache
        e = led2.section()["docs"]["d"]
        assert e["lag_changes"] == 0
        assert e["behind_since"] is None
        assert e["lag_s"] == 0.0
    finally:
        _close(svc)


def test_receive_split_counts_duplicates_and_redundancy():
    a, b, ca, cb, drain = _pair()
    try:
        a.apply_changes("d", [_chg("x", 1)])
        drain()
        # re-deliver the same change out of band: the clock covers it,
        # so it must count as duplicate wire work, not useful
        from automerge_tpu.sync.frames import encode_frame
        cb.receive_msg({"docId": "d", "clock": {"x": 1},
                        "frame": encode_frame([_chg("x", 1)])})
        snap = metrics.snapshot()
        assert snap["sync_conn_changes_delivered"] >= 1
        assert snap["sync_conn_changes_duplicate"] == 1
        red = b.doc_ledger.redundancy()
        assert red["duplicate"] == 1
        assert red["ratio"] == round(1 / red["useful"], 4)
        lane = b.doc_ledger.section()["docs"]["d"]["peers"]["A"]
        assert lane["recv_duplicate"] == 1
        assert lane["bytes_received"] > 0
    finally:
        _close(a, b)


def test_changes_ahead_of_frontier_count_useful_not_duplicate():
    """A causally-early delivery (seq 2 before seq 1) is NEW information
    — it parks in the causal queue but is not wasted wire work."""
    a, b, ca, cb, drain = _pair(wire="json")
    try:
        cb.receive_msg({"docId": "d", "clock": {"x": 2},
                        "changes": [_chg("x", 2).to_dict()]})
        snap = metrics.snapshot()
        assert snap.get("sync_conn_changes_delivered") == 1
        assert "sync_conn_changes_duplicate" not in snap
    finally:
        _close(a, b)


def test_bounded_memory_evicts_lru_into_aggregate_keeping_laggards():
    led = DocLedger(label="n", top_k=8)

    class _Conn:
        peer_label = "W"
    conn = _Conn()
    # make doc "behind0" permanently lagging (no doc_set -> use explicit
    # receive counts only; mark behind via the entry directly)
    for i in range(8):
        led.record_receive(f"cold{i}", conn, 1, 0)
    with led._lock:
        led._docs["cold0"].behind_since = time.time()   # the laggard
    for i in range(6):
        led.record_receive(f"hot{i}", conn, 2, 1)
    sec = led.section()
    assert sec["tracked"] <= 8
    assert sec["evictions"] == 6
    assert metrics.snapshot()["obs_doc_evictions"] == 6
    # the lagging doc survived every eviction scan; the evicted docs'
    # counts folded into the aggregate bucket
    assert "cold0" in sec["docs"]
    assert sec["aggregate"]["docs"] == 6
    assert sec["aggregate"]["recv_useful"] == 6
    # global redundancy counters survive eviction untouched
    assert sec["redundancy"]["useful"] == 8 + 12
    assert sec["redundancy"]["duplicate"] == 6


def test_section_is_pure_and_json_clean_and_resets():
    a, b, ca, cb, drain = _pair()
    try:
        for s in (1, 2):
            a.apply_changes("d", [_chg("x", s)])
            drain()
        from automerge_tpu.utils.gcpause import gc_paused
        with gc_paused():   # a collection in between would be counted
            s1 = metrics.snapshot()
            s2 = metrics.snapshot()
        assert s1 == s2, "snapshot export must be pure (no wall reads)"
        assert json.loads(json.dumps(s1)) == s1
        nodes = s1["docledger"]["nodes"]
        assert set(nodes) == {"A", "B"}
        assert nodes["B"]["docs"]["d"]["peers"]["A"]["recv_useful"] == 2
        with gc_paused():
            metrics.reset()
            assert metrics.snapshot() == {}
        # a still-live service re-registers on its next mutation
        a.apply_changes("d", [_chg("x", 3)])
        drain()
        assert "docledger" in metrics.snapshot()
    finally:
        _close(a, b)


def test_gauges_refresh_on_mutation_cadence():
    led = DocLedger(label="n")

    class _Conn:
        peer_label = "W"
    conn = _Conn()
    for i in range(docledger.GAUGE_REFRESH):
        led.record_receive("d", conn, 1, 1)
    snap = metrics.snapshot()
    assert snap["obs_doc_tracked"] == 1
    assert snap["obs_doc_redundancy_ratio"] == 1.0
    assert snap["obs_doc_ledger_s_count"] >= 1
    assert snap["obs_doc_ledger_s_sum"] > 0


def test_epoch_buffer_visibility_and_doc_count():
    from automerge_tpu.native.wire import changes_to_columns
    from automerge_tpu.sync.epochs import EpochIngestBuffer
    buf = EpochIngestBuffer()
    cols = changes_to_columns([_chg("x", 1)])
    buf.append("d", cols, None)
    buf.append("d", cols, None)
    buf.append("e", cols, None)
    assert buf.doc_count("d") == 2
    assert buf.doc_count("e") == 1
    assert buf.doc_count("zz") == 0
    entries = buf.seal()
    EpochIngestBuffer.resolve([e.ticket for e in entries])
    assert buf.doc_count("d") == 0


def test_disabled_plane_is_inert(monkeypatch):
    monkeypatch.setenv("AMTPU_DOCLEDGER", "0")
    docledger._reload_for_tests()
    try:
        svc = EngineDocSet(backend="rows")
        try:
            assert svc.doc_ledger is None
            q = []
            conn = Connection(svc, q.append, wire="columnar")
            assert conn._ledger is None
            conn.open()
            svc.apply_changes("d", [_chg("x", 1)])
            snap = metrics.snapshot()
            assert "docledger" not in snap
            assert not any(k.startswith("obs_doc_") for k in snap)
            assert not any(k.startswith("sync_conn_changes_")
                           for k in snap)
        finally:
            svc.close()
    finally:
        monkeypatch.delenv("AMTPU_DOCLEDGER")
        docledger._reload_for_tests()


def test_service_admission_stamps_and_forget_conn():
    a, b, ca, cb, drain = _pair()
    try:
        a.apply_changes("d", [_chg("x", 1)])
        drain()
        e = a.doc_ledger.section()["docs"]["d"]
        assert e["admitted"] == 1
        assert e["last_admit_at"] is not None
        assert "B" in e["peers"]
        ca.close()
        assert "B" not in a.doc_ledger.section()["docs"]["d"]["peers"]
    finally:
        _close(a, b)


def test_tcp_per_kind_byte_accounting():
    """Exact wire bytes split by kind over a real TCP pair, plus the
    ledger lanes riding the same sync."""
    from automerge_tpu.sync.tcp import TcpSyncClient, TcpSyncServer
    a, b = EngineDocSet(backend="rows"), EngineDocSet(backend="rows")
    server = TcpSyncServer(a, wire="columnar").start()
    client = TcpSyncClient(b, "127.0.0.1", server.port,
                           wire="columnar").start()
    try:
        b.apply_changes("d", [_chg("x", 1)])
        deadline = time.time() + 10
        while time.time() < deadline:
            if a.clock_of("d") if "d" in a.doc_ids else {}:
                break
            time.sleep(0.02)
        assert a.clock_of("d") == {"x": 1}
        snap = metrics.snapshot()
        by_kind = {k: v for k, v in snap.items()
                   if k.startswith("sync_conn_bytes_")}
        assert "sync_conn_bytes_sent{kind=frame}" in by_kind
        assert "sync_conn_bytes_sent{kind=clock}" in by_kind
        assert by_kind["sync_conn_bytes_sent{kind=frame}"] > \
            by_kind["sync_conn_bytes_sent{kind=clock}"] / 10
    finally:
        client.close()
        server.close()
        _close(a, b)


def test_refresh_clocks_restamps_against_locked_read():
    svc = EngineDocSet(backend="rows")
    try:
        led = svc.doc_ledger

        class _Conn:
            peer_label = "W"
        for s in (1, 2):
            svc.apply_changes("d", [_chg("x", s)])
        led.record_advert("d", _Conn(), {"x": 5})
        # peek may or may not be warm; the explicit refresh must settle
        # the deficit exactly against the locked read
        assert led.refresh_clocks() >= 1
        e = led.section()["docs"]["d"]
        assert e["lag_changes"] == 3
    finally:
        _close(svc)


def test_chaos_doc_stall_counts_and_adverts_still_flow(monkeypatch):
    from automerge_tpu.utils import chaos
    monkeypatch.setenv("AMTPU_CHAOS_STALL_DOC", "victim")
    chaos.reload()
    try:
        a, b, ca, cb, drain = _pair()
        try:
            a.apply_changes("victim", [_chg("x", 1)])
            a.apply_changes("ok", [_chg("x", 1)])
            drain()
            # the untouched doc synced; the victim's changes never left,
            # but its clock advert DID (chaos never blinds instruments)
            assert b.clock_of("ok") == {"x": 1}
            assert "victim" not in b.doc_ids
            snap = metrics.snapshot()
            assert snap["sync_frames_dropped"] >= 1
            assert snap["obs_chaos_injected{fault=doc_stall}"] >= 1
            lane_b = b.doc_ledger.section()["docs"]["victim"]
            assert lane_b["lag_changes"] == 1
            lane_a = a.doc_ledger.section()["docs"]["victim"]
            assert lane_a["peers"]["B"]["drops"] >= 1
        finally:
            _close(a, b)
    finally:
        monkeypatch.delenv("AMTPU_CHAOS_STALL_DOC")
        chaos.reload()


def test_chaos_stall_doc_inert_when_unset():
    from automerge_tpu.utils import chaos
    assert os.environ.get("AMTPU_CHAOS_STALL_DOC") is None
    chaos.reload()
    assert chaos.stall_doc(None, "any") is False
    assert not chaos.enabled()


# -- the flush's round call -------------------------------------------------

ROUND_NOW = 1_000.0


class _Peer:
    peer_label = "W"


def _admit_as_the_seed_did(led, doc_id, n_changes):
    """`note_admit` as it stood before the round call: one `_entry_locked`
    (LRU touch or insert, one eviction by `_evict_locked`'s rule, the gauge
    cadence) and the stamps, a document."""
    with led._lock:
        e = led._entry_locked(doc_id)
        e.admitted += int(n_changes)
        e.last_admit_at = ROUND_NOW
        if e.behind_since is not None:
            e.lag_s = max(0.0, ROUND_NOW - e.behind_since)


def _one_by_one(led, counts):
    for d, n in counts.items():
        led.note_admit(d, n)


def _seed_one_by_one(led, counts):
    for d, n in counts.items():
        _admit_as_the_seed_did(led, d, n)


def _held(n, behind=()):
    """`n` tracked docs with a peer lane each (counts a fold must carry),
    those at the LRU positions `behind` lagging."""
    def prepare(led):
        for i in range(n):
            led.record_receive(f"held{i}", _Peer(), i + 1, 1, nbytes=10)
        with led._lock:
            for i in behind:
                e = led._docs[f"held{i}"]
                e.behind_since, e.lag_changes = ROUND_NOW - 5.0 - i, 2
                e.lag_s = 5.0 + i
    return prepare


def _round(new, hits=(), start=0):
    """`new` docs the table has never held, in order, with tracked doc
    `held<h>` placed at position `at` for each (at, h) of `hits`."""
    docs = [f"new{start + i}" for i in range(new)]
    for at, h in sorted(hits):
        docs.insert(at, f"held{h}")
    return {d: 1 + i % 3 for i, d in enumerate(docs)}


# top_k is 32; every case ends on a multiple of GAUGE_REFRESH mutations, so
# the last refresh of the one-by-one feed reads the state the round leaves
ROUND_CASES = {
    # 8 tracked, a round of 24 (4 of them tracked): nothing is evicted
    "smaller-than-top-k": (_held(8), [_round(20, [(0, 3), (5, 0), (9, 7),
                                                  (23, 4)])]),
    # 32 tracked, a round of 96: held30 and held2 are touched while they
    # are still in the table, held20 after it was folded (made anew, folded
    # again), held9 late enough to survive the round
    "larger-none-behind": (_held(32), [_round(92, [(1, 30), (2, 2),
                                                   (40, 20), (90, 9)])]),
    # the same with 18 lagging docs at the LRU end (a scan of EVICT_SCAN
    # finds no other victim) and one in the middle; held1 is touched behind
    "larger-entries-behind": (_held(32, behind=(*range(18), 25)),
                              [_round(92, [(1, 30), (2, 1), (40, 20),
                                           (90, 9)])]),
    # two rounds of 64 that share docs: the second finds the first's last
    # 32 tracked, touches some and makes the folded ones anew
    "repeated-across-two-rounds": (_held(0), [
        _round(64), {f"new{i}": 2 for i in (*range(60, 30, -3),
                                            *range(100, 154))}]),
}


def _ledger_state(led):
    with led._lock:
        entries = [(d, e.admitted, e.touches, e.last_admit_at,
                    e.behind_since, e.lag_s, e.lag_changes,
                    {lbl: (pv.recv_useful, pv.recv_duplicate,
                           pv.bytes_received) for lbl, pv in e.peers.items()})
                   for d, e in led._docs.items()]
        state = {"entries": entries, "aggregate": dict(led._agg),
                 "mutations": led._mutations, "evictions": led._evictions}
    snap = metrics.snapshot()
    state["gauges"] = {k: v for k, v in snap.items()
                       if k.startswith("obs_doc_")
                       and not k.startswith("obs_doc_ledger_s")}
    sec = led.section()
    sec.pop("self_s")
    state["section"] = sec
    return state


@pytest.mark.parametrize("case", sorted(ROUND_CASES))
def test_a_round_call_leaves_what_one_call_a_document_leaves(
        case, monkeypatch):
    prepare, rounds = ROUND_CASES[case]
    monkeypatch.setattr(time, "time", lambda: ROUND_NOW)
    left = {}
    for feed in ("seed", "one-by-one", "round"):
        metrics.reset()
        led = DocLedger(label="n", top_k=32)
        prepare(led)
        refreshes = []
        real = led._refresh_gauges_locked
        led._refresh_gauges_locked = lambda: (refreshes.append(1), real())
        for counts in rounds:
            before = len(refreshes)
            {"seed": _seed_one_by_one, "one-by-one": _one_by_one,
             "round": DocLedger.note_admit_round}[feed](led, counts)
            if feed == "round":
                assert len(refreshes) - before <= 1
        assert led._mutations % docledger.GAUGE_REFRESH == 0
        assert refreshes
        left[feed] = _ledger_state(led)
    assert left["round"] == left["seed"]
    assert left["one-by-one"] == left["seed"]
    if case != "smaller-than-top-k":
        assert left["round"]["evictions"] >= 64
        assert left["round"]["gauges"]["obs_doc_evictions"] \
            == left["round"]["evictions"]
        assert len(left["round"]["entries"]) == 32


def test_a_round_of_one_keeps_the_gauge_cadence_of_one_refresh_in_32():
    led = DocLedger(label="n")
    refreshes = []
    real = led._refresh_gauges_locked
    led._refresh_gauges_locked = lambda: (refreshes.append(1), real())
    for i in range(3 * docledger.GAUGE_REFRESH):
        led.note_admit_round({f"d{i % 5}": 1})
    assert len(refreshes) == 3
    led.note_admit_round({})                 # no document, no mutation
    assert led._mutations == 3 * docledger.GAUGE_REFRESH


def test_a_round_that_raises_midway_leaves_entries_only():
    """A bad count in the middle of a round larger than `top_k`: the call
    raises, and the table it leaves holds `_DocEntry`s alone (no plain
    count of a doc that was waiting for its entry), within `top_k`, with
    every doc that came before the bad one accounted for; the ledger's
    reads and the next round work."""
    led = DocLedger(label="n", top_k=8)
    _held(8)(led)
    counts = {f"new{i}": 1 for i in range(40)}
    counts["new20"] = "not a count"
    with pytest.raises(ValueError):
        led.note_admit_round(counts)
    with led._lock:
        assert all(e.__class__ is docledger._DocEntry
                   for e in led._docs.values())
        assert list(led._docs) == [f"new{i}" for i in range(12, 20)]
        assert led._agg["docs"] == led._evictions == 20
        assert led._agg["admitted"] == 12       # the 8 held admitted none
        led._refresh_gauges_locked()
    assert led.section(k=8)["tracked"] == 8
    led.note_admit_round({f"new{i}": 1 for i in range(20, 40)})
    assert len(led.section(k=8)["docs"]) == 8
