"""Epoch-batched ingestion (sync/epochs.py + the service's epoch mode):
group-commit coalescing, snapshot-read consistency under concurrent
writers, flush-failure ticket/retry semantics, the oplag buffer_wait
stage, and flusher thread lifecycle."""

import threading
import time

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet
from automerge_tpu.utils import metrics, oplag

from tests.test_rows_service import oracle_hash


def wire_change(actor, seq, key="k", value=0):
    from automerge_tpu.native.wire import changes_to_columns
    return changes_to_columns([Change(actor=actor, seq=seq, deps={},
                                      ops=[Op("set", ROOT_ID, key=key,
                                              value=value)])])


def chs(actor, n, key="k"):
    return [Change(actor=actor, seq=s, deps={},
                   ops=[Op("set", ROOT_ID, key=key, value=s)])
            for s in range(1, n + 1)]


def test_epoch_mode_is_the_rows_default():
    e = EngineDocSet(backend="rows")
    assert e.ingest_mode == "epoch"
    assert e._epoch is not None and e._flusher is not None
    # docs-major applies inline regardless of the requested mode
    r = EngineDocSet(backend="resident", ingest_mode="epoch")
    assert r.ingest_mode == "locked"
    with pytest.raises(ValueError, match="ingest_mode"):
        EngineDocSet(backend="rows", ingest_mode="bogus")


def test_apply_returns_flushed_and_readable():
    """The synchronous contract survives the buffered admission path:
    when apply_changes returns, the change is engine truth."""
    e = EngineDocSet(backend="rows")
    cs = chs("A", 3)
    e.apply_changes("d", cs)
    assert e._pending == {} and e._epoch.empty()
    assert e.clock_of("d") == {"A": 3}
    got = e.missing_changes("d", {})
    assert {(c.actor, c.seq) for c in got} == {("A", s) for s in (1, 2, 3)}
    assert np.uint32(e.hashes()["d"]) == oracle_hash(cs)
    e.close()


def test_concurrent_writers_group_commit_and_converge():
    """N writer threads through one epoch-mode service: fewer rounds than
    ingresses (group commit), every doc converges to the oracle, and no
    writer ever waits on the service lock."""
    am.metrics.reset()
    e = EngineDocSet(backend="rows")
    n_writers, n_ops = 4, 40
    docs = {w: f"w{w}" for w in range(n_writers)}
    for w, d in docs.items():
        e.apply_changes(d, chs(f"W{w}", 1))
    m0 = metrics.snapshot()
    errs = []

    def writer(w):
        try:
            for s in range(2, n_ops + 2):
                e.apply_columns(docs[w], wire_change(f"W{w}", s, value=s))
        except BaseException as exc:
            errs.append(exc)

    ts = [threading.Thread(target=writer, args=(w,), daemon=True,
                           name=f"t-epoch-w{w}") for w in range(n_writers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    m1 = metrics.snapshot()
    rounds = (m1.get("sync_rounds_flushed", 0)
              - m0.get("sync_rounds_flushed", 0))
    total = n_writers * n_ops
    assert 0 < rounds < total, (rounds, total)   # coalescing happened
    assert (m1.get("sync_ops_ingested", 0)
            - m0.get("sync_ops_ingested", 0)) == total
    wait_key = "sync_lock_wait_s{lock=service}_sum"
    assert (m1.get(wait_key, 0.0) - m0.get(wait_key, 0.0)) < 0.5
    for w, d in docs.items():
        want = oracle_hash(chs(f"W{w}", n_ops + 1))
        assert np.uint32(e.hashes()[d]) == want, d
    e.close()


def test_abandoned_async_handle_still_gossips():
    """The drain thread's gossip backstop: an apply_columns_async caller
    that drops its handle without waiting must not strand _admit_notify
    — attached handlers still hear about the admission, and a handler
    that re-enters apply ON the drain thread takes the inline locked
    path instead of deadlocking the drainer on its own ticket."""
    e = EngineDocSet(backend="rows")
    e.apply_changes("d", chs("A", 1))
    e.apply_changes("other", chs("B", 1))
    seen = []

    def handler(doc_id, handle):
        seen.append(doc_id)
        if doc_id == "d" and seen.count("d") == 1:
            # re-entrant apply on whatever thread runs the gossip
            e.apply_columns("other", wire_change("B", 2, value=2))

    e.handlers.append(handler)
    e.apply_columns_async("d", wire_change("A", 2, value=2))  # abandoned
    deadline = time.time() + 10.0
    while ("d" not in seen or e.clock_of("other") != {"B": 2}) \
            and time.time() < deadline:
        time.sleep(0.01)
    assert "d" in seen, "abandoned ingress never gossiped"
    assert e.clock_of("other") == {"B": 2}, "re-entrant apply lost"
    assert e.clock_of("d") == {"A": 2}
    e.close()


def test_sync_apply_gossips_on_the_calling_thread_before_return():
    """A synchronous apply's ticket is CLAIMED, so the flusher's gossip
    backstop stays off the round: when apply_columns returns, the
    admission gossip has been delivered — and by the applying thread
    itself (a relayed send must run inside the serve span that
    triggered it; a single-threaded test pumping an in-memory wire
    must find the message already queued). This is the regression
    pin for the backstop/writer delivery race."""
    e = EngineDocSet(backend="rows")
    seen = []
    e.handlers.append(
        lambda doc_id, handle: seen.append(
            (doc_id, threading.current_thread().name)))
    for i in range(1, 21):
        e.apply_columns("d", wire_change("A", i, value=i))
        assert ("d", threading.current_thread().name) in seen, \
            f"ingress {i}: gossip not delivered on the caller by return"
        assert not any(t.startswith("amtpu-flusher") for _, t in seen), \
            "flusher backstop stole a claimed round's gossip"
        seen.clear()
    e.close()


def test_refill_probe_waits_on_growth_never_on_a_clock():
    """The flusher's pre-seal refill window (_refill_probe) yields the
    GIL only while the buffer is still GROWING: a static or empty
    buffer quiesces on the first poll (no latency tax on a solo or
    synchronous writer), the probe never consumes entries (sealing is
    _drain_epochs_once's job), and a pathological never-waiting append
    flood cannot hold it past the hard cap (_REFILL_CAP_S)."""
    e = EngineDocSet(backend="rows")
    try:
        t0 = time.perf_counter()
        for _ in range(50):
            e._refill_probe()           # empty: nothing to wait for
        assert time.perf_counter() - t0 < 0.25
        e._epoch.append("d", wire_change("A", 1, value=1), None)
        t0 = time.perf_counter()
        e._refill_probe()               # static: one no-growth poll
        assert time.perf_counter() - t0 < 0.25
        assert e._epoch.count() == 1    # probe observed, never sealed
        stop = threading.Event()

        def flood():
            s = 2
            while not stop.is_set():
                e._epoch.append("d", wire_change("A", s, value=s), None)
                s += 1

        th = threading.Thread(target=flood, daemon=True)
        th.start()
        try:
            t0 = time.perf_counter()
            e._refill_probe()           # growth every poll: cap bounds it
            assert time.perf_counter() - t0 < 0.25
        finally:
            stop.set()
            th.join()
    finally:
        e.close()


def test_seal_is_one_atomic_cut_across_stripes():
    """seal() holds ALL stripe locks across the swap: with one stripe
    lock held externally, a blocked seal must not have drained ANY
    stripe (a per-stripe sequential drain would let a writer's later
    append seal into an earlier round than its prior append to an
    already-drained stripe, breaking per-thread durability order)."""
    from automerge_tpu.sync.epochs import EpochIngestBuffer

    buf = EpochIngestBuffer()
    # two docs landing in different stripes
    docs = {}
    for i in range(64):
        d = f"doc{i}"
        k = buf._stripes.index(buf._stripe_of(d))
        docs.setdefault(k, d)
        if len(docs) >= 2:
            break
    (k_lo, d_lo), (k_hi, d_hi) = sorted(docs.items())[:2]
    buf.append(d_lo, None, None)
    buf.append(d_hi, None, None)
    sealed = []
    with buf._stripes[k_hi].lock:        # block the cut at a LATER stripe
        t = threading.Thread(target=lambda: sealed.append(buf.seal()),
                             daemon=True)
        t.start()
        time.sleep(0.15)
        assert t.is_alive()
        # nothing swapped yet: the earlier stripe still holds its entry
        assert len(buf._stripes[k_lo].entries) == 1
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert {e.doc_id for e in sealed[0]} == {d_lo, d_hi}
    assert buf.empty() and not buf.has(d_lo) and not buf.has(d_hi)


def test_concurrent_readers_see_only_sealed_epochs():
    """Readers racing writers never observe torn state: every clock_of /
    missing_changes pair is internally consistent (the served changes
    cover exactly the served clock), and mid-flight reads equal a
    quiesced re-read once writers stop."""
    e = EngineDocSet(backend="rows")
    e.apply_changes("d", chs("A", 1))
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            clk = e.clock_of("d")
            got = e.missing_changes("d", {})
            seqs = sorted(c.seq for c in got if c.actor == "A")
            # no torn reads: the log is a contiguous prefix 1..k and the
            # clock read beside it is some (possibly older/newer) k'
            if seqs != list(range(1, len(seqs) + 1)):
                bad.append(("gap", seqs))
            if clk.get("A", 0) > 60:
                bad.append(("clock overrun", clk))

    def writer():
        for s in range(2, 61):
            e.apply_columns("d", wire_change("A", s, value=s))

    rs = [threading.Thread(target=reader, daemon=True, name=f"t-rd{i}")
          for i in range(2)]
    w = threading.Thread(target=writer, daemon=True, name="t-wr")
    for t in rs:
        t.start()
    w.start()
    w.join()
    stop.set()
    for t in rs:
        t.join(timeout=10)
    assert not bad, bad[:3]
    # quiesced re-read agrees with the final mid-flight view
    assert e.clock_of("d") == {"A": 60}
    assert len(e.missing_changes("d", {})) == 60
    e.close()


def test_flush_failure_reaches_writer_and_retry_succeeds():
    """A pre-admission flush failure resolves the waiting writer's ticket
    with the error, leaves the round in _pending (buffer intact for
    retry), and an explicit flush() retries it to truth."""
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    cs = chs("A", 2)
    real = rset.dispatch_round_frames
    rset.dispatch_round_frames = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("budget precheck failed"))
    with pytest.raises(RuntimeError, match="precheck"):
        e.apply_changes("d", cs)
    rset.dispatch_round_frames = real
    assert "d" in e._pending          # restored for retry
    e.flush()
    assert e._pending == {}
    assert np.uint32(e.hashes()["d"]) == oracle_hash(cs)
    e.close()


def test_reads_mid_flush_equal_quiesced_reread():
    """hashes()/missing_changes served while a flush is in flight equal
    a quiesced re-read: a slow engine apply cannot expose half-applied
    state to the read surface."""
    e = EngineDocSet(backend="rows")
    e.apply_changes("d", chs("A", 1))
    rset = e._resident
    real = rset.dispatch_round_frames
    entered = threading.Event()

    def slow(*a, **k):
        entered.set()
        time.sleep(0.15)
        return real(*a, **k)

    rset.dispatch_round_frames = slow
    t = threading.Thread(
        target=lambda: e.apply_columns("d", wire_change("A", 2, value=2)),
        daemon=True, name="t-slow-writer")
    t.start()
    assert entered.wait(5.0)
    # mid-flush reads: block-and-observe or serve the pre-flush snapshot
    # — either way internally consistent
    clk = e.clock_of("d")
    assert clk.get("A") in (1, 2)
    t.join(timeout=10)
    rset.dispatch_round_frames = real
    assert e.clock_of("d") == {"A": 2}
    assert len(e.missing_changes("d", {})) == 2
    assert np.uint32(e.hashes()["d"]) == oracle_hash(chs("A", 2))
    e.close()


def test_snapshot_read_cache_serves_and_invalidates():
    """Repeated clock_of/missing_changes reads of an untouched doc serve
    from the snapshot cache (sync_reads_cached moves); an admission
    invalidates, and the next read sees the new truth."""
    am.metrics.reset()
    e = EngineDocSet(backend="rows")
    e.apply_changes("d", chs("A", 2))
    e.clock_of("d")                    # fills the cache
    m0 = metrics.snapshot().get("sync_reads_cached", 0)
    for _ in range(3):
        assert e.clock_of("d") == {"A": 2}
        assert len(e.missing_changes("d", {"A": 1})) == 1
    m1 = metrics.snapshot().get("sync_reads_cached", 0)
    assert m1 - m0 >= 5
    e.apply_columns("d", wire_change("A", 3, value=3))
    assert e.clock_of("d") == {"A": 3}           # invalidated + refilled
    assert len(e.missing_changes("d", {})) == 3
    e.close()


def test_oplag_buffer_wait_stage_records():
    """Sampled epoch-mode ingresses record the buffer_wait stage (append
    -> seal) alongside the existing flush stages."""
    am.metrics.reset()
    oplag.set_sample_rate(1)
    try:
        e = EngineDocSet(backend="rows")
        e.apply_changes("d", chs("A", 2))
        snap = metrics.snapshot()
        for stage in ("buffer_wait", "queue_wait", "flush", "origin_total"):
            assert snap.get(f"sync_op_lag_s{{stage={stage}}}_count",
                            0) >= 1, stage
        assert "buffer_wait" in snap["oplag"]["stages"]
        e.close()
    finally:
        oplag.set_sample_rate(None)
        am.metrics.reset()


def test_locked_mode_still_available_and_converges():
    e = EngineDocSet(backend="rows", ingest_mode="locked")
    assert e._epoch is None and e._flusher is None
    cs = chs("A", 3)
    e.apply_changes("d", cs)
    assert np.uint32(e.hashes()["d"]) == oracle_hash(cs)
    assert e.clock_of("d") == {"A": 3}


def test_flusher_thread_named_and_joined_on_close():
    """The flusher spawns lazily with the amtpu-flusher-<shard> name
    (flight-recorder attribution), and close() joins it."""
    s = ShardedEngineDocSet(n_shards=2)
    s.apply_changes("doc-a", chs("A", 1))
    s.apply_changes("doc-b", chs("B", 1))
    names = {t.name for t in threading.enumerate()}
    assert any(n.startswith("amtpu-flusher-") for n in names), names
    s.close()
    deadline = time.time() + 5.0
    while time.time() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.name.startswith("amtpu-flusher-")]
        if not alive:
            break
        time.sleep(0.02)
    assert not alive


def test_flusher_exits_after_idle_linger_and_respawns(monkeypatch):
    """An idle flusher exits past the linger window (no thread leak per
    service) and a later ingress respawns a fresh one."""
    e = EngineDocSet(backend="rows")
    e._flusher._linger_s = 0.05
    e.apply_changes("d", chs("A", 1))
    t1 = e._flusher._thread
    assert t1 is not None and t1.is_alive()
    deadline = time.time() + 5.0
    while time.time() < deadline and e._flusher._thread is not None:
        time.sleep(0.02)
    assert e._flusher._thread is None
    t1.join(timeout=5.0)
    e.apply_columns("d", wire_change("A", 2, value=2))    # respawns
    assert e.clock_of("d") == {"A": 2}
    e.close()


def test_batch_still_one_round_in_epoch_mode():
    am.metrics.reset()
    e = EngineDocSet(backend="rows")
    with e.batch():
        for i in range(5):
            e.apply_changes(f"d{i}", chs(f"W{i}", 1))
    snap = am.metrics.snapshot()
    assert snap.get("sync_rounds_flushed", 0) == 1, snap
    for i in range(5):
        assert np.uint32(e.hashes()[f"d{i}"]) == oracle_hash(chs(f"W{i}", 1))
    e.close()


def test_sharded_concurrent_writers_audit_green():
    """Concurrent multi-writer load on a sharded node: the convergence
    audit surface still reports consistent per-shard digests, and an
    injected divergence is still isolated through the epoch-snapshot
    read path."""
    from automerge_tpu.sync.audit import state_digest

    s = ShardedEngineDocSet(n_shards=2)
    docs = [f"doc{i}" for i in range(6)]
    for d in docs:
        s.apply_changes(d, chs("B", 1, key="base"))

    def writer(w):
        for seq in range(2, 12):
            s.apply_columns(docs[w % len(docs)], wire_change("B", seq, value=seq))

    ts = [threading.Thread(target=writer, args=(w,), daemon=True,
                           name=f"t-shw{w}") for w in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    st = s.audit_state()
    assert set(st) == {"0", "1"}
    # digests recompute identically from the doc-level surface
    for shard, info in st.items():
        detail = s.audit_shard_state(shard)
        assert state_digest(detail["hashes"]) == info["digest"]
    # inject divergence in one shard's engine and re-read: the digest of
    # exactly that shard moves
    victim = s.shard_of(docs[0])
    rset = victim._resident
    i = rset.doc_index[docs[0]]
    rset._mark_dirty([i]) if hasattr(rset, "_mark_dirty") else None
    before = s.audit_state()
    victim.apply_changes(docs[0], [Change(
        actor="EVIL", seq=1, deps={},
        ops=[Op("set", ROOT_ID, key="x", value=666)])])
    after = s.audit_state()
    vlabel = victim._shard
    assert after[vlabel]["digest"] != before[vlabel]["digest"]
    other = [k for k in after if k != vlabel][0]
    assert after[other]["digest"] == before[other]["digest"]
    s.close()


def test_apply_columns_async_pipeline():
    """The pipelined admission surface: tickets resolve with flush
    durability, in-order per writer thread, and errors reach the
    awaiting caller; locked-mode services degrade to synchronous apply
    with a pre-resolved handle."""
    e = EngineDocSet(backend="rows")
    pend = [e.apply_columns_async("d", wire_change("A", s, value=s))
            for s in range(1, 6)]
    for p in pend:
        p.wait()
    # wait is idempotent: a repeat wait on a resolved ticket returns
    # immediately instead of parking on the already-consumed futex
    for p in pend:
        p.wait()
    assert e.clock_of("d") == {"A": 5}
    assert np.uint32(e.hashes()["d"]) == oracle_hash(chs("A", 5))
    # error propagation: a failing flush reaches the awaiting caller
    rset = e._resident
    if rset._native is not None:
        real = rset.dispatch_round_frames
        rset.dispatch_round_frames = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("boom async"))
        p = e.apply_columns_async("d", wire_change("A", 6, value=6))
        with pytest.raises(RuntimeError, match="boom async"):
            p.wait()
        with pytest.raises(RuntimeError, match="boom async"):
            p.wait()                    # repeat wait re-raises, no hang
        rset.dispatch_round_frames = real
        e.flush()                       # retry drains the restored round
        assert e.clock_of("d") == {"A": 6}
    e.close()
    # locked mode: synchronous fallback, handle pre-resolved
    el = EngineDocSet(backend="rows", ingest_mode="locked")
    h = el.apply_columns_async("d", wire_change("B", 1, value=1))
    assert h.done
    h.wait()
    assert el.clock_of("d") == {"B": 1}
