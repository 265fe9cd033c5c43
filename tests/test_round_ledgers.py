"""A round's tail writes its ledgers once a round.

The rows flush (`_flush_pending_inner_locked`, its `publish` phase) hands
the round's per-document submitted-change counts to one
`DocLedger.note_admit_round` and one `tenantledger.note_ingress_round`,
where it used to call `note_admit` and `note_ingress` a document. Held
here, at the service: a batch of 200 documents makes one call of each and
none of the per-document ones (they are patched to raise: this is where the
one-call-a-round fact is held, and no counter repeats it), the ledgers
export what a service fed the same changes one `apply_changes` each
exports, a flush that fails before admission writes neither ledger, and a
single `apply_changes` flushes through the same two calls. What a round call leaves in a ledger, entry for
entry, is held in tests/test_docledger.py and tests/test_tenantledger.py.
"""

import pytest

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.sync import tenantledger
from automerge_tpu.sync.docledger import DocLedger
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import metrics

N_DOCS = 200


@pytest.fixture(autouse=True)
def _clean():
    metrics.reset()
    yield
    metrics.reset()


def doc_id(i: int) -> str:
    # three named tenants and the default one
    return f"tenant/t{i % 3}/d{i}" if i % 4 else f"plain{i}"


def one_op(i: int, seq: int = 1):
    return [Change("storm", seq, {}, [Op("set", ROOT_ID, key="n", value=i)])]


def counter(name: str) -> int:
    return sum(v for k, v in metrics.snapshot().items()
               if k == name or k.startswith(name + "{"))


@pytest.fixture
def calls(monkeypatch):
    """The round calls of both ledgers, recorded; the per-document calls
    raise."""
    seen = {"doc": [], "tenant": []}

    def never(*a, **kw):
        raise AssertionError("a per-document ledger call at a rows flush")

    real_doc = DocLedger.note_admit_round
    real_tenant = tenantledger.TenantLedger.note_ingress_round

    def doc_round(self, counts):
        seen["doc"].append(dict(counts))
        real_doc(self, counts)

    def tenant_round(self, counts):
        seen["tenant"].append(dict(counts))
        real_tenant(self, counts)

    monkeypatch.setattr(DocLedger, "note_admit", never)
    monkeypatch.setattr(tenantledger.TenantLedger, "note_ingress", never)
    monkeypatch.setattr(tenantledger, "note_ingress", never)
    monkeypatch.setattr(DocLedger, "note_admit_round", doc_round)
    monkeypatch.setattr(tenantledger.TenantLedger, "note_ingress_round",
                        tenant_round)
    return seen


def exports(svc) -> dict:
    """What the two ledgers export of admission, without the stamps and
    the self-time, which no two runs share."""
    doc = svc.doc_ledger.section(k=svc.doc_ledger.top_k)
    doc.pop("self_s")
    for e in doc["docs"].values():
        assert e.pop("last_admit_at") is not None
    ten = tenantledger.ledger().section()
    return {"doc": doc,
            "tenant": {"admitted_total": ten["admitted_total"],
                       "tracked": ten["tracked"],
                       "tenants": {t: (a["admitted"], a["admit_events"],
                                       a["ingress_share_pct"])
                                   for t, a in ten["tenants"].items()}}}


def test_a_batch_writes_each_ledger_once_and_leaves_what_single_calls_leave(
        calls):
    svc = EngineDocSet(backend="rows")
    try:
        with svc.batch():
            for i in range(N_DOCS):
                svc.apply_changes(doc_id(i), one_op(i))
            # a second change for some, and a second part for one
            for i in range(0, N_DOCS, 7):
                svc.apply_changes(doc_id(i), one_op(i, seq=2))
            assert calls == {"doc": [], "tenant": []}
        want = {doc_id(i): 2 if i % 7 == 0 else 1 for i in range(N_DOCS)}
        assert calls["doc"] == [want] and calls["tenant"] == [want]
        assert list(calls["doc"][0]) == list(want)      # admission order
        assert counter("sync_rounds_flushed") == 1
        batch = exports(svc)
    finally:
        svc.close()
    assert batch["doc"]["tracked"] == svc.doc_ledger.top_k
    assert batch["doc"]["aggregate"]["docs"] == N_DOCS - svc.doc_ledger.top_k
    assert batch["tenant"]["admitted_total"] == sum(want.values())
    assert sum(e for _a, e, _s in batch["tenant"]["tenants"].values()) \
        == N_DOCS

    # the same changes, one apply_changes each and a flush each, in the
    # order the batch admitted its documents
    metrics.reset()
    calls["doc"].clear()
    calls["tenant"].clear()
    single = EngineDocSet(backend="rows")
    try:
        for i in range(N_DOCS):
            chs = one_op(i) + (one_op(i, seq=2) if i % 7 == 0 else [])
            single.apply_changes(doc_id(i), chs)
        assert calls["doc"] == [{d: n} for d, n in want.items()]
        assert calls["tenant"] == calls["doc"]
        assert counter("sync_rounds_flushed") == N_DOCS
        singles = exports(single)
    finally:
        single.close()
    singles["doc"]["label"] = batch["doc"]["label"]
    assert singles == batch


def test_a_flush_that_fails_before_admission_writes_neither_ledger(calls):
    svc = EngineDocSet(backend="rows")
    try:
        rset = svc._resident
        real = rset.dispatch_round_frames

        def boom(frames, interpret=None, compactor=None):
            raise RuntimeError("batch would blow the VMEM budget")

        rset.dispatch_round_frames = boom
        with pytest.raises(RuntimeError, match="VMEM"):
            with svc.batch():
                for i in range(N_DOCS):
                    svc.apply_changes(doc_id(i), one_op(i))
        assert calls == {"doc": [], "tenant": []}
        assert counter("sync_rounds_flushed") == 0
        assert svc.doc_ledger.section() is None
        assert tenantledger.ledger()._admitted_total == 0
        # the retry admits the restored round and writes both, once
        rset.dispatch_round_frames = real
        svc.flush()
        assert [len(c) for c in calls["doc"]] == [N_DOCS]
        assert [len(c) for c in calls["tenant"]] == [N_DOCS]
        assert counter("sync_rounds_flushed") == 1
    finally:
        svc.close()


@pytest.mark.parametrize("ingest_mode", ["epoch", "locked"])
def test_a_single_apply_changes_takes_the_same_two_calls(calls, ingest_mode):
    svc = EngineDocSet(backend="rows", ingest_mode=ingest_mode)
    try:
        svc.apply_changes("tenant/a/d", one_op(1) + one_op(1, seq=2))
        svc.apply_changes("plain", one_op(2))
        assert calls["doc"] == [{"tenant/a/d": 2}, {"plain": 1}]
        assert calls["tenant"] == calls["doc"]
        assert counter("sync_rounds_flushed") == 2
        # a duplicate delivery admits nothing: the flush is counted, the
        # round it hands the ledgers is empty and writes nothing
        mutations = svc.doc_ledger._mutations
        svc.apply_changes("plain", one_op(2))
        assert calls["doc"][2:] == calls["tenant"][2:] == [{}]
        assert svc.doc_ledger._mutations == mutations
        assert tenantledger.ledger()._admitted_total == 3
        docs = svc.doc_ledger.section()["docs"]
        assert docs["tenant/a/d"]["admitted"] == 2
        assert docs["plain"]["admitted"] == 1
    finally:
        svc.close()


def test_every_shard_of_a_sharded_round_writes_its_own_ledger_once(calls):
    from automerge_tpu.sync.sharded_service import ShardedEngineDocSet

    svc = ShardedEngineDocSet(n_shards=4)
    try:
        with svc.batch():
            for i in range(N_DOCS):
                svc.apply_changes(doc_id(i), one_op(i))
        assert sorted(len(c) for c in calls["doc"]) == sorted(
            sum(1 for i in range(N_DOCS)
                if svc.shard_of(doc_id(i)) is sh) for sh in svc.shards)
        assert sum(len(c) for c in calls["tenant"]) == N_DOCS
        assert len(calls["doc"]) == len(calls["tenant"]) == 4
        snap = metrics.snapshot()
        assert all(snap.get(f"sync_rounds_flushed{{shard={k}}}") == 1
                   for k in range(4))
    finally:
        svc.close()
