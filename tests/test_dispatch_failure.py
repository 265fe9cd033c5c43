"""Dispatch-failure recovery on the rows sync service.

A device dispatch can fail AFTER host admission succeeded. The engine keeps rows_host as an exact pre-dispatch mirror, so
the correct recovery is: keep the admission (change_log / clocks / mirror are
consistent), drop the device buffer, and rebuild it lazily. The typed error's
``admission_complete`` flag tells the service whether anything from the round
could have been lost: a pure dispatch failure (True) retries nothing, while a
mid-admission rebuild (False) restores EVERY doc of the round — the engine's
(actor, seq) dedup drops the already-admitted prefix idempotently, so the
retry admits exactly the missing remainder and no ingress is ever silently
lost (ADVICE r4 medium, service.py:260).

Pre-admission failures (budget precheck, malformed frames) restore exactly
the docs whose changes did not admit, so a later flush can retry them.
"""

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.engine.resident_rows import DeviceDispatchError
from automerge_tpu.sync.service import EngineDocSet

from tests.test_rows_service import oracle_hash


def make_doc(i):
    d = am.change(am.init("W"), lambda x, i=i: am.assign(
        x, {"n": i, "xs": [i, i + 1]}))
    return d._doc.opset.get_missing_changes({})


def test_dispatch_failure_keeps_admission_and_recovers():
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")

    chs0 = make_doc(0)
    e.apply_changes("d0", chs0)     # healthy ingress first

    # Fail the NEXT device dispatch only; admission runs before it.
    real = rset._dispatch_final
    calls = {"n": 0}

    def failing(trip_list, route, interpret):
        calls["n"] += 1
        raise RuntimeError("device lost mid-dispatch")

    rset._dispatch_final = failing
    chs1 = make_doc(1)
    try:
        # the service swallows DeviceDispatchError: truth was admitted
        e.apply_changes("d1", chs1)
    finally:
        rset._dispatch_final = real
    assert calls["n"] == 1

    # not re-queued, logged as admitted, clocks advanced
    assert e._pending == {}
    assert len(rset.change_log[rset.doc_index["d1"]]) == len(chs1)
    assert e.clock_of("d1").get("W", 0) == len(chs1)
    # replaying the same ingress is a duplicate-drop, not a double-apply
    e.apply_changes("d1", chs1)
    assert len(rset.change_log[rset.doc_index["d1"]]) == len(chs1)

    # the device buffer was dropped and marked dirty; the next read
    # re-uploads the host mirror and converges to the oracle
    h = e.hashes()
    assert np.uint32(h["d0"]) == oracle_hash(chs0)
    assert np.uint32(h["d1"]) == oracle_hash(chs1)
    assert e.materialize("d1")["data"]["n"] == 1


def test_engine_raises_typed_error_and_marks_dirty():
    from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
    from automerge_tpu.native.wire import changes_to_columns
    from automerge_tpu.sync.frames import round_from_parts

    rset = ResidentRowsDocSet(["d0"])
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")
    real = rset._dispatch_final
    rset._dispatch_final = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("boom"))
    chs = make_doc(7)
    frame = round_from_parts({"d0": [changes_to_columns(chs)]})
    with pytest.raises(DeviceDispatchError):
        rset.apply_round_frames([frame])
    rset._dispatch_final = real
    assert rset.rows_dev is None and rset._dirty
    # log records the admission; the mirror re-uploads to the oracle hash
    assert len(rset.change_log[rset.doc_index["d0"]]) == len(chs)
    assert np.uint32(rset.hashes()[0]) == oracle_hash(chs)


def test_readback_failure_recovers_at_next_read():
    """The dispatch is async: a device failure often surfaces at the
    np.asarray readback barrier inside hashes(), not at dispatch time.
    The same mirror recovery must engage there."""
    from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
    from automerge_tpu.native.wire import changes_to_columns
    from automerge_tpu.sync.frames import round_from_parts

    rset = ResidentRowsDocSet(["d0"])
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")
    chs = make_doc(5)
    frame = round_from_parts({"d0": [changes_to_columns(chs)]})
    rset.apply_round_frames([frame])

    class BoomHandle:
        def __array__(self, *a, **k):
            raise RuntimeError("device lost during readback")

    rset._hash_handle = BoomHandle()
    with pytest.raises(DeviceDispatchError):
        rset.hashes()
    assert rset.rows_dev is None and rset._dirty
    # next read re-uploads the mirror and recomputes
    assert np.uint32(rset.hashes()[0]) == oracle_hash(chs)


def test_midadmission_failure_rebuilds_from_log():
    """A failure between admission and the mirror scatter (e.g. a grow
    MemoryError) leaves change_log ahead of rows_host; the engine must
    rebuild from the log rather than let them diverge."""
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")

    chs0 = make_doc(0)
    e.apply_changes("d0", chs0)

    real = rset._cols_triplets
    rset._cols_triplets = lambda enc: (_ for _ in ()).throw(
        MemoryError("grow failed mid-scatter"))
    chs1 = make_doc(1)
    e.apply_changes("d1", chs1)   # DeviceDispatchError swallowed by service
    rset = e._resident            # rebuild replaced engine internals

    # admitted in the (rebuilt) log; the round returns to pending because a
    # mid-admission rebuild cannot prove the whole round reached the log
    # (admission_complete=False) — the retry is a pure duplicate-drop
    assert "d1" in e._pending
    assert len(rset.change_log[rset.doc_index["d1"]]) == len(chs1)
    e.flush()
    assert e._pending == {}
    assert len(rset.change_log[rset.doc_index["d1"]]) == len(chs1)
    h = e.hashes()
    assert np.uint32(h["d0"]) == oracle_hash(chs0)
    assert np.uint32(h["d1"]) == oracle_hash(chs1)
    # replay of the same ingress is still a duplicate-drop
    e.apply_changes("d1", chs1)
    assert len(rset.change_log[rset.doc_index["d1"]]) == len(chs1)
    # the rebuild swapped in fresh internals, clearing the monkeypatch
    assert "_cols_triplets" not in rset.__dict__


def test_partial_admission_restores_whole_round_and_dedups():
    """A mid-admission rebuild (admission_complete=False) can leave an
    arbitrary suffix of the round unprocessed — neither logged nor queued.
    The service must restore EVERY doc of the round (ADVICE r4 medium); on
    retry the already-admitted prefix duplicate-drops against the real
    clocks and only the lost remainder admits — no silent loss, no
    double-apply."""
    from automerge_tpu.native.wire import changes_to_columns
    from automerge_tpu.sync.frames import round_from_parts

    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    e.add_doc("a")
    e.add_doc("b")
    chs_a, chs_b = make_doc(1), make_doc(2)

    real = rset.dispatch_round_frames

    def partial(frames, interpret=None, compactor=None):
        # really admit doc a (log + clocks + mirror), then fail before b
        real([round_from_parts({"a": [changes_to_columns(chs_a)]})])
        raise DeviceDispatchError("failed after admitting a, before b",
                                  admission_complete=False)

    rset.dispatch_round_frames = partial
    with e.batch():
        e.apply_changes("a", chs_a)
        e.apply_changes("b", chs_b)
    rset.dispatch_round_frames = real

    # the whole round returns to pending: b's changes were lost mid-round,
    # a's replay is a safe duplicate-drop
    assert "a" in e._pending and "b" in e._pending
    assert len(rset.change_log[rset.doc_index["a"]]) == len(chs_a)
    assert len(rset.change_log[rset.doc_index["b"]]) == 0
    e.flush()
    assert e._pending == {}
    assert len(rset.change_log[rset.doc_index["a"]]) == len(chs_a)
    assert len(rset.change_log[rset.doc_index["b"]]) == len(chs_b)
    assert np.uint32(e.hashes()["a"]) == oracle_hash(chs_a)
    assert np.uint32(e.hashes()["b"]) == oracle_hash(chs_b)


def test_pure_dispatch_failure_retries_nothing():
    """admission_complete=True: the whole round reached host truth, so the
    service must NOT re-queue it (the retry would be pure wasted encode
    work on every device hiccup)."""
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")
    real = rset.dispatch_round_frames

    def dispatch_fail(frames, interpret=None, compactor=None):
        real(frames)   # full admission + mirror succeed
        raise DeviceDispatchError("device lost at dispatch",
                                  admission_complete=True)

    rset.dispatch_round_frames = dispatch_fail
    chs = make_doc(4)
    e.apply_changes("d4", chs)
    rset.dispatch_round_frames = real

    assert e._pending == {}
    assert len(rset.change_log[rset.doc_index["d4"]]) == len(chs)
    assert np.uint32(e.hashes()["d4"]) == oracle_hash(chs)


def test_poisoned_when_rebuild_is_impossible():
    """If the rebuild replay hits the same deterministic failure, the node
    must fail loudly on every later apply/read instead of serving hashes
    that silently drop admitted changes."""
    from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
    from automerge_tpu.native.wire import changes_to_columns
    from automerge_tpu.sync.frames import round_from_parts

    rset = ResidentRowsDocSet(["d0"])
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    rset._rebuilding = True   # simulate being inside a rebuild replay
    rset._cols_triplets = lambda enc: (_ for _ in ()).throw(
        MemoryError("deterministic capacity failure"))
    frame = round_from_parts({"d0": [changes_to_columns(make_doc(1))]})
    with pytest.raises(MemoryError):
        rset.apply_round_frames([frame])
    with pytest.raises(RuntimeError, match="no longer reflects"):
        rset.hashes()
    with pytest.raises(RuntimeError, match="no longer reflects"):
        rset.apply_round_frames([frame])


def test_preadmission_failure_restores_unadmitted_docs():
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")

    chs = make_doc(3)
    real = rset.dispatch_round_frames

    def precheck_boom(frames, interpret=None, compactor=None):
        raise RuntimeError("batch would blow the VMEM budget")

    rset.dispatch_round_frames = precheck_boom
    with pytest.raises(RuntimeError, match="VMEM"):
        e.apply_changes("d3", chs)
    rset.dispatch_round_frames = real

    # nothing admitted -> the ingress was restored for retry
    assert "d3" in e._pending
    assert len(rset.change_log[rset.doc_index["d3"]]) == 0
    e.flush()
    assert e._pending == {}
    assert np.uint32(e.hashes()["d3"]) == oracle_hash(chs)


# -- a round whose device work is split in a dispatch and a collect half ------


def _eager_service_with_a_current_copy(monkeypatch, n=40):
    """A rows service on the chip's road (reconcile at the flush) whose
    rounds plan and are declined, as in the benchmark's cells; `n`
    documents loaded, the device copy current."""
    from automerge_tpu.engine import dispatch

    monkeypatch.setattr(dispatch, "_megabatch", True)
    monkeypatch.setattr(
        dispatch, "plan_round",
        lambda rset, idxs: dispatch.RoundPlan("per_doc", list(idxs)))
    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")
    e._lazy_resolved = True
    rset.lazy_dispatch = False
    docs = {f"d{i}": make_doc(i) for i in range(n)}
    with e.batch():
        for d, chs in docs.items():
            e.apply_changes(d, chs)
    assert rset._dev_current and e._pending == {}
    return e, rset, docs


def _second_change(chs):
    """The next change of the writer of `chs` (make_doc's one change)."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    return [Change(actor="W", seq=len(chs) + 1, deps={},
                   ops=[Op("set", ROOT_ID, key="n", value=-1)])]


def test_a_failure_at_the_collect_is_swallowed_and_the_next_read_recovers(
        monkeypatch):
    """The device fails after the round's reconcile was dispatched and
    surfaces where the hashes are read back, behind the service's tail: the
    same recovery as at any readback (copy dropped, lanes dirty), the
    round admitted and counted once, its callers released."""
    from automerge_tpu.utils import metrics

    e, rset, docs = _eager_service_with_a_current_copy(monkeypatch)

    class BoomHandle:
        def block_until_ready(self):
            raise RuntimeError("device lost during readback")

    real = rset.collect_round
    admits = []
    real_admit = e.doc_ledger.note_admit_round

    def collect(*a, **k):
        lanes, _h = rset._unsettled
        rset._unsettled = (lanes, BoomHandle())
        return real(*a, **k)

    rset.collect_round = collect
    e.doc_ledger.note_admit_round = lambda counts: (
        admits.append(dict(counts)), real_admit(counts))[1]
    m0 = metrics.snapshot()
    touched = [f"d{i}" for i in range(8)]
    with e.batch():         # returns: the service swallows the failure
        for d in touched:
            e.apply_changes(d, _second_change(docs[d]))
    rset.collect_round = real
    e.doc_ledger.note_admit_round = real_admit
    m1 = metrics.snapshot()

    def delta(k):
        return m1.get(k, 0) - m0.get(k, 0)

    assert e._pending == {}
    assert delta("rows_dispatch_failed") == 1
    assert delta("sync_rounds_flushed") == 1
    assert delta("sync_ops_ingested") == 8
    assert admits == [dict.fromkeys(touched, 1)]
    for d in touched:
        assert len(rset.change_log[rset.doc_index[d]]) == len(docs[d]) + 1
    # the copy dropped, nothing unsettled, the round's lanes still dirty
    assert rset.rows_dev is None and rset._dirty and rset._unsettled is None
    assert {rset.doc_index[d] for d in touched} <= rset._doc_dirty
    h = e.hashes()
    for d in docs:
        want = docs[d] + _second_change(docs[d]) * (d in touched)
        assert np.uint32(h[d]) == oracle_hash(want), d
    # replaying the round is a duplicate-drop
    e.apply_changes("d0", _second_change(docs["d0"]))
    assert len(rset.change_log[rset.doc_index["d0"]]) == len(docs["d0"]) + 1
    e.close()


def test_a_failed_put_of_compacted_lanes_drops_the_copy_and_replays(
        monkeypatch):
    """A round takes a document past the op rows; the precheck compacts it
    on the host and writes its lane into the current copy, and that put
    fails. The copy is dropped (it still holds the lane's old columns), the
    round, of which nothing was admitted, returns to pending, and its
    replay, which has nothing left to compact, converges to the oracle."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    from automerge_tpu.engine import resident_rows
    from automerge_tpu.utils import metrics

    e, rset, docs = _eager_service_with_a_current_copy(monkeypatch)
    log = list(docs["d0"])

    def overwrite():
        return [Change(actor="W", seq=len(log) + 1, deps={},
                       ops=[Op("set", ROOT_ID, key="n", value=len(log))])]
    i = rset.doc_index["d0"]
    while rset.op_count[i] < rset.cap_ops:    # dominated writes pile up
        ch = overwrite()
        e.apply_changes("d0", ch)
        log += ch
    assert rset._dev_current and e._pending == {}
    dims = rset.dims()

    def put_fails(*a):
        raise RuntimeError("device lost at the lanes' put")
    monkeypatch.setattr(resident_rows, "_put_cols", put_fails)
    m0 = metrics.snapshot()
    ch = overwrite()
    e.apply_changes("d0", ch)
    log += ch
    m1 = metrics.snapshot()
    monkeypatch.undo()

    def delta(k):
        return m1.get(k, 0) - m0.get(k, 0)

    # the mirror was compacted, nothing of the round admitted
    assert rset.op_count[i] < dims[0]
    assert delta("engine_kernels_dispatched{kernel=put_lanes}") == 1
    assert delta("rows_dispatch_failed") == 1
    assert delta("sync_ops_ingested") == 0
    assert rset.rows_dev is None and rset._dirty and not rset._dev_current
    assert "d0" in e._pending
    assert len(rset.change_log[i]) == len(log) - 1
    e.flush()
    assert e._pending == {} and rset.dims() == dims
    assert len(rset.change_log[i]) == len(log)
    h = e.hashes()
    for d in docs:
        assert np.uint32(h[d]) == oracle_hash(log if d == "d0" else docs[d])
    e.close()


def test_a_failure_at_the_early_scatter_retries_nothing(monkeypatch):
    """The scatter that goes out before the router runs fails: a pure
    dispatch failure like any other (admission_complete=True). Nothing is
    re-queued, the router is never asked, the next read recovers."""
    from automerge_tpu.engine import dispatch
    from automerge_tpu.utils import metrics

    e, rset, docs = _eager_service_with_a_current_copy(monkeypatch)
    asked = []
    real_route = dispatch.reconcile_route
    monkeypatch.setattr(
        dispatch, "reconcile_route",
        lambda *a, **k: (asked.append(a), real_route(*a, **k))[1])
    real = rset._scatter_round
    calls = []

    def scatter_fails(trip_list, n_lanes):
        calls.append(n_lanes)
        raise RuntimeError("device lost at the scatter")

    rset._scatter_round = scatter_fails
    m0 = metrics.snapshot()
    touched = [f"d{i}" for i in range(8)]
    with e.batch():
        for d in touched:
            e.apply_changes(d, _second_change(docs[d]))
    rset._scatter_round = real
    m1 = metrics.snapshot()
    assert calls == [8] and asked == []
    assert e._pending == {}
    assert m1.get("rows_dispatch_failed", 0) \
        - m0.get("rows_dispatch_failed", 0) == 1
    assert m1.get("sync_ops_ingested", 0) \
        - m0.get("sync_ops_ingested", 0) == 8
    assert rset.rows_dev is None and rset._dirty and rset._unsettled is None
    for d in touched:
        assert len(rset.change_log[rset.doc_index[d]]) == len(docs[d]) + 1
    h = e.hashes()
    for d in docs:
        want = docs[d] + _second_change(docs[d]) * (d in touched)
        assert np.uint32(h[d]) == oracle_hash(want), d
    e.close()
