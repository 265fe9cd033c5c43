"""EngineDocSet(backend="rows"): the sync service running on the docs-minor
streaming engine — Connection-driven columnar sync, coalesced round-frame
ingress (batch()), re-serving lagging peers from the engine's admitted log,
and dynamic document-axis growth."""

import numpy as np

import automerge_tpu as am
from automerge_tpu.engine.batchdoc import apply_batch
from automerge_tpu.sync.connection import Connection
from automerge_tpu.sync.service import EngineDocSet


def oracle_hash(changes):
    _, _, out = apply_batch([changes])
    return np.uint32(np.asarray(out["hash"])[0])


def two_replica_trace():
    a = am.change(am.init("A"),
                  lambda d: am.assign(d, {"x": 1, "tags": ["p", "q"]}))
    b = am.merge(am.init("B"), a)
    a = am.change(a, lambda d: d.__setitem__("x", 5))
    b = am.change(b, lambda d: d["tags"].append("r"))
    merged = am.merge(a, b)
    return (a._doc.opset.get_missing_changes({}),
            b._doc.opset.get_missing_changes({}),
            merged._doc.opset.get_missing_changes({}))


def drain(qa, ca, qb, cb, rounds=30):
    for _ in range(rounds):
        moved = False
        while qa:
            cb.receive_msg(qa.pop(0))
            moved = True
        while qb:
            ca.receive_msg(qb.pop(0))
            moved = True
        if not moved:
            break


def test_rows_nodes_converge_over_columnar_wire():
    chs_a, chs_b, chs_all = two_replica_trace()
    qa, qb = [], []
    ea = EngineDocSet(backend="rows")
    eb = EngineDocSet(backend="rows")
    ca = Connection(ea, qa.append, wire="columnar")
    cb = Connection(eb, qb.append, wire="columnar")
    ea.add_doc("d")
    eb.add_doc("d")
    ca.open()
    cb.open()
    ea.apply_changes("d", chs_a)
    eb.apply_changes("d", chs_b)
    drain(qa, ca, qb, cb)
    want = oracle_hash(chs_all)
    assert np.uint32(ea.hashes()["d"]) == want
    assert np.uint32(eb.hashes()["d"]) == want
    assert ea.materialize("d") == eb.materialize("d")


def test_rows_batch_coalesces_to_one_round():
    am.metrics.reset()
    e = EngineDocSet(backend="rows")
    docs = {}
    for i in range(6):
        docs[f"d{i}"] = am.change(am.init("W"), lambda d, i=i: am.assign(
            d, {"n": i}))
    with e.batch():
        for did, doc in docs.items():
            e.apply_changes(did, doc._doc.opset.get_missing_changes({}))
    snap = am.metrics.snapshot()
    # six ingresses, ONE round applied (batched or per-round is shape-
    # dependent; the coalescing itself is what this asserts)
    assert snap.get("sync_rounds_flushed", 0) == 1, snap
    for did, doc in docs.items():
        want = oracle_hash(doc._doc.opset.get_missing_changes({}))
        assert np.uint32(e.hashes()[did]) == want


def test_rows_missing_changes_reserves_lagging_peer():
    chs_a, _chs_b, _ = two_replica_trace()
    e = EngineDocSet(backend="rows")
    e.add_doc("d")
    e.apply_changes("d", chs_a)
    got = e.missing_changes("d", {})
    assert {(c.actor, c.seq) for c in got} == {(c.actor, c.seq)
                                              for c in chs_a}
    # suffix query: peer already has A:1
    got2 = e.missing_changes("d", {"A": 1})
    assert all(c.seq > 1 or c.actor != "A" for c in got2)
    clk = e.clock_of("d")
    assert clk.get("A", 0) >= 2


def test_rows_document_axis_growth():
    """Adding docs past the 128-lane pad re-layouts the rows mirror; state
    stays intact and new docs reconcile correctly."""
    e = EngineDocSet(backend="rows")
    hashes_want = {}
    with e.batch():
        for i in range(130):
            d = am.change(am.init("G"), lambda x, i=i: am.assign(
                x, {"n": i, "xs": [i]}))
            chs = d._doc.opset.get_missing_changes({})
            e.apply_changes(f"d{i}", chs)
            hashes_want[f"d{i}"] = oracle_hash(chs)
    h = e.hashes()
    for did, want in hashes_want.items():
        assert np.uint32(h[did]) == want, did
    # a later edit to an early doc still lands after the growth
    clk = e.clock_of("d0")
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    ch = Change("G", clk["G"] + 1, {}, (Op("set", ROOT_ID, key="n",
                                           value=999),))
    e.apply_changes("d0", [ch])
    assert e.materialize("d0")["data"]["n"] == 999


def test_many_actors_grow_clock_bands_with_parity():
    """20 actors accrete onto one doc through the rows service: each new
    actor triggers rank remap and eventually actor-capacity growth (the
    clock_op band is actors-major, so cap_actors doubling re-layouts the
    row buffer). Hash parity with the oracle must hold throughout."""
    e = EngineDocSet(backend="rows")
    e.add_doc("d")
    base = am.change(am.init("actor00"), lambda d: am.assign(
        d, {"n": 0, "xs": [1]}))
    e.apply_changes("d", base._doc.opset.get_missing_changes({}))
    merged = base
    for k in range(1, 20):
        prev_clock = dict(merged._doc.opset.clock)
        mine = am.change(am.merge(am.init(f"actor{k:02d}"), merged),
                         lambda d, k=k: d.__setitem__(f"f{k % 5}", k))
        delta = mine._doc.opset.get_missing_changes(prev_clock)
        e.apply_changes("d", delta)
        merged = mine
    want = oracle_hash(merged._doc.opset.get_missing_changes({}))
    assert np.uint32(e.hashes()["d"]) == want
    assert e._resident.cap_actors >= 20
    assert e.materialize("d")["data"]["n"] == 0
