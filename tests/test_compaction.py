"""Causally-stable compaction (engine/compaction.py, VERDICT r4 missing #3).

The reference never reclaims history (op_set.js:250 appends forever; its
only compaction analog is save/load, automerge.js:223-226) and degrades
gradually; the rows engine instead has a hard VMEM admission wall
(pack.rows_dims_eligible). These tests pin the compaction contract:

- convergence hashes are bit-identical across compacted and uncompacted
  replicas holding the same visible state;
- admission continues across the compaction (clock dicts never shrink);
- tombstoned elements reclaim their device band slot only below the
  known-peer clock floor, ghosts keep ordering future siblings correctly;
- anchors at compacted elements are rejected loudly BEFORE admission;
- the sync service auto-compacts on budget pressure, letting a single
  long-lived document edit far past the pre-compaction budget (the soak),
  while a bare engine without the service hook hits RowsBudgetError.
"""

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.engine.resident_rows import (
    CompactionAnchorError, ResidentRowsDocSet, RowsBudgetError)
from automerge_tpu.sync.connection import Connection
from automerge_tpu.sync.service import EngineDocSet

from tests.test_rows_service import drain, oracle_hash


def changes_of(doc):
    return doc._doc.opset.get_missing_changes({})


def build_history():
    d = am.init("alice")
    d = am.change(d, lambda x: x.__setitem__("t", am.Text()))
    d = am.change(d, lambda x: x["t"].insert_at(0, *"hello world"))
    for k in range(30):
        d = am.change(d, lambda x, k=k: x.__setitem__("n", k))
    d = am.change(d, lambda x: [x["t"].delete_at(0) for _ in range(6)])
    return d


def test_hash_parity_and_reclaim():
    d = build_history()
    chs = changes_of(d)
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", chs)
    rset = e._resident
    i = rset.doc_index["doc"]
    h0 = np.uint32(e.hashes()["doc"])
    assert h0 == oracle_hash(chs)

    stats = rset.compact({"doc": dict(rset.tables[i].clock)})["doc"]
    # 30 dominated overwrites + all make/ins rows + below-floor DELs gone;
    # the 6 deleted chars ghosted out of their band slots
    assert stats["ops_after"] < stats["ops_before"]
    assert stats["elems_after"] == 5           # "world"
    assert int(rset.op_count[i]) == stats["ops_after"]
    assert np.uint32(e.hashes()["doc"]) == h0   # hash is visible-state-only
    assert "".join(e.materialize("doc")["data"]["t"]) == "world"


def test_admission_and_linearization_after_compaction():
    d = build_history()
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    floor = dict(rset.tables[rset.doc_index["doc"]].clock)
    rset.compact({"doc": floor})

    # front, middle and map edits on top of the compacted state: the
    # ghosts' ordering keys must keep new inserts linearized exactly as an
    # uncompacted replica would
    d2 = am.change(d, lambda x: x["t"].insert_at(0, *"HI "))
    d2 = am.change(d2, lambda x: x["t"].insert_at(5, "X"))
    d2 = am.change(d2, lambda x: x.__setitem__("n", 999))
    e.apply_changes("doc", [c for c in changes_of(d2)
                            if c.seq > floor.get(c.actor, 0)])
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(d2))
    assert "".join(e.materialize("doc")["data"]["t"]) == "HI woXrld"


def test_a_list_compacted_to_nothing_takes_inserts_again():
    """Every element of a list deleted below the floor: compaction empties
    its insert log, and the next inserts (at the head) admit into it, with
    no rebuild from the log, and converge with an uncompacted replica."""
    from automerge_tpu.utils import metrics
    d = am.change(am.init("alice"), lambda x: x.__setitem__("t", am.Text()))
    d = am.change(d, lambda x: x["t"].insert_at(0, *"hello world"))
    d = am.change(d, lambda x: [x["t"].delete_at(0) for _ in range(11)])
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    i = rset.doc_index["doc"]
    floor = dict(rset.tables[i].clock)
    assert rset.compact({"doc": floor})["doc"]["elems_after"] == 0
    assert len(rset.lists.log(i, 0)) == 0

    rebuilt = metrics.snapshot().get("rows_log_rebuilt", 0)
    d2 = am.change(d, lambda x: x["t"].insert_at(0, *"xy"))
    e.apply_changes("doc", [c for c in changes_of(d2)
                            if c.seq > floor.get(c.actor, 0)])
    assert metrics.snapshot().get("rows_log_rebuilt", 0) == rebuilt
    assert len(rset.lists.log(i, 0)) == 2
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(d2))
    assert "".join(e.materialize("doc")["data"]["t"]) == "xy"


def test_concurrent_conflicts_survive_compaction():
    """Mutually-concurrent candidates (winner + conflicts) are visible
    state; both must survive and hash identically to a fresh replica."""
    a = am.change(am.init("A"), lambda x: x.__setitem__("k", "from-a"))
    b = am.merge(am.init("B"), a)
    a2 = am.change(a, lambda x: x.__setitem__("k", "a-wins?"))
    b2 = am.change(b, lambda x: x.__setitem__("k", "b-wins?"))
    merged = am.merge(a2, b2)
    chs = changes_of(merged)

    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", chs)
    rset = e._resident
    i = rset.doc_index["doc"]
    h0 = np.uint32(e.hashes()["doc"])
    assert h0 == oracle_hash(chs)
    stats = rset.compact({"doc": dict(rset.tables[i].clock)})["doc"]
    assert np.uint32(e.hashes()["doc"]) == h0
    # both concurrent assigns are candidates: neither may be reclaimed
    kept = stats["ops_after"]
    assert kept >= 2


def test_floor_gates_del_reclaim_for_straggler_inserts():
    """A tombstone ABOVE the floor keeps its slot: a straggler's insert
    anchored at it must still admit and converge with an uncompacted
    replica."""
    base = am.change(am.init("A"), lambda x: x.__setitem__("t", am.Text()))
    base = am.change(base, lambda x: x["t"].insert_at(0, *"abc"))
    # straggler B forks here, knowing element 'b'
    fork = am.merge(am.init("B"), base)
    # A deletes 'b' — but the floor stays at the fork point (B hasn't
    # acknowledged the delete)
    a2 = am.change(base, lambda x: x["t"].delete_at(1))
    floor = {c.actor: c.seq for c in changes_of(base)}

    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(a2))
    rset = e._resident
    stats = rset.compact({"doc": floor})["doc"]
    assert stats["elems_after"] == 3   # tombstone 'b' above floor: kept

    # B concurrently inserts after 'b' (it still sees "abc")
    b2 = am.change(fork, lambda x: x["t"].insert_at(2, "X"))
    merged = am.merge(a2, b2)
    e.apply_changes("doc", [c for c in changes_of(b2) if c.actor == "B"])
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(merged))
    assert "".join(e.materialize("doc")["data"]["t"]) == \
        "".join(merged["t"])


def test_peer_ahead_blocks_tombstone_reclaim():
    """An advertised clock can cover a tombstone while the peer still has
    in-flight changes generated BEFORE it saw the delete — one of them may
    anchor at the tombstone. Until this node is a superset of every peer,
    the floor must exclude tombstone reclaim entirely."""
    base = am.change(am.init("A"), lambda x: x.__setitem__("t", am.Text()))
    base = am.change(base, lambda x: x["t"].insert_at(0, *"abc"))
    fork = am.merge(am.init("B"), base)
    # B inserts after 'b' without having seen the delete (in flight)...
    b2 = am.change(fork, lambda x: x["t"].insert_at(2, "X"))
    # ...A deletes 'b' and B's later advertisement covers the delete
    a2 = am.change(base, lambda x: x["t"].delete_at(1))
    merged = am.merge(a2, b2)

    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(a2))
    rset = e._resident
    i = rset.doc_index["doc"]
    own = dict(rset.tables[i].clock)
    # B advertises: saw everything of A AND has one change of its own we
    # have not admitted -> peer is ahead -> empty floor, no ghosting
    e.note_peer_clock("B", "doc", {**own, "B": 1})
    floor = e._compaction_floor_locked("doc")
    assert floor == {}
    stats = rset.compact({"doc": floor})["doc"]
    assert stats["elems_after"] == 3   # tombstone 'b' kept

    # the in-flight insert arrives and converges
    e.apply_changes("doc", [c for c in changes_of(b2) if c.actor == "B"])
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(merged))


def test_pins_protect_pending_round_anchors():
    """Anchors referenced by a coalesced-but-unadmitted round must keep
    their slots through a mid-flush compaction (service passes them as
    pins)."""
    d = build_history()
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    i = rset.doc_index["doc"]
    floor = dict(rset.tables[i].clock)
    # pin one of the deleted chars' eids: with the pin it must keep its
    # slot (and its anchor chain), without it it would ghost
    pinned = "alice:3"
    stats = rset.compact({"doc": floor}, pins={"doc": {pinned}})["doc"]
    assert pinned not in rset.lists.ghosts(i)
    assert stats["elems_after"] > 5   # the pin (and its chain) retained
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(d))


def test_anchor_at_compacted_element_rejected_preadmission():
    d = build_history()
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    i = rset.doc_index["doc"]
    rset.compact({"doc": dict(rset.tables[i].clock)})
    assert rset.lists.ghosts(i)

    # forge a nonconforming change anchored at a ghosted element
    ghost = sorted(rset.lists.ghosts(i))[0]
    from automerge_tpu.core.change import Change, Op
    # the text object id, from the change that created it
    text_obj = changes_of(d)[1].ops[0].obj
    bad = Change(actor="alice", seq=len(changes_of(d)) + 1,
                 deps={}, ops=[Op(action="ins", obj=text_obj,
                                  key=ghost, elem=999)])
    log_before = len(rset.change_log[i])
    with pytest.raises(CompactionAnchorError):
        e.apply_changes("doc", [bad])
    # pre-admission: nothing recorded, node healthy, later ingress fine
    assert len(rset.change_log[i]) == log_before
    d2 = am.change(d, lambda x: x.__setitem__("ok", True))
    e.apply_changes("doc", [changes_of(d2)[-1]])
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(d2))


def test_peer_floor_limits_then_allows_reclaim():
    """A registered lagging peer holds the floor down; once it advertises
    a caught-up clock the same compaction reclaims."""
    d = build_history()
    chs = changes_of(d)
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", chs)
    rset = e._resident
    i = rset.doc_index["doc"]

    e.note_peer_clock("peer-1", "doc", {"alice": 2})  # saw only the insert
    floors = {"doc": e._compaction_floor_locked("doc")}
    assert floors["doc"]["alice"] == 2
    stats = rset.compact(floors)["doc"]
    # deletes are above the floor: tombstones keep their slots
    assert stats["elems_after"] == 11
    h0 = np.uint32(e.hashes()["doc"])
    assert h0 == oracle_hash(chs)

    e.note_peer_clock("peer-1", "doc", {"alice": chs[-1].seq})
    stats = rset.compact({"doc": e._compaction_floor_locked("doc")})["doc"]
    assert stats["elems_after"] == 5
    assert np.uint32(e.hashes()["doc"]) == h0


def test_compacted_node_syncs_with_fresh_peer():
    """The change log is untouched by row compaction: a fresh reference-
    protocol peer catches up from the compacted node and converges."""
    d = build_history()
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    rset.compact({"doc": dict(rset.tables[rset.doc_index["doc"]].clock)})

    from automerge_tpu.sync.docset import DocSet
    fresh = DocSet()
    qa, qb = [], []
    ca = Connection(e, qa.append)
    cb = Connection(fresh, qb.append)
    ca.open()
    cb.open()
    cb.send_msg("doc", {})
    drain(qa, ca, qb, cb)
    got = fresh.get_doc("doc")
    assert got is not None
    assert "".join(got["t"]) == "world"
    assert got["n"] == 29


def test_rebuild_from_log_after_compaction_is_budget_safe():
    """A mid-admission failure on a compacted doc rebuilds from the FULL
    log; the chunked replay re-compacts between chunks instead of
    poisoning on RowsBudgetError."""
    d = build_history()
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    i = rset.doc_index["doc"]
    rset.compact({"doc": dict(rset.tables[i].clock)})

    rset._cols_triplets = lambda enc: (_ for _ in ()).throw(
        MemoryError("grow failed mid-scatter"))
    d2 = am.change(d, lambda x: x.__setitem__("post", 1))
    e.apply_changes("doc", [changes_of(d2)[-1]])   # swallowed; rebuild
    e.flush()
    rset = e._resident
    assert np.uint32(e.hashes()["doc"]) == oracle_hash(changes_of(d2))


def _edit_round(d, rng, n_ins=8, n_del=8, n_sets=8):
    def step(x):
        t = x["t"]
        for _ in range(n_ins):
            t.insert_at(rng.randrange(len(t) + 1),
                        chr(97 + rng.randrange(26)))
        for _ in range(n_del):
            if len(t) > 1:
                t.delete_at(rng.randrange(len(t)))
        for k in range(n_sets):
            x[f"f{rng.randrange(4)}"] = rng.randrange(1000)
    return am.change(d, step)


def test_soak_long_lived_doc_past_vmem_budget():
    """The headline contract: a single document keeps editing far past the
    pre-compaction budget. The service auto-compacts on RowsBudgetError
    (floor = own clock: no peers registered) and hash parity vs the
    uncompacted oracle holds at every checkpoint; a bare engine fed the
    same history with no compaction hook raises RowsBudgetError."""
    import random
    rng = random.Random(7)

    d = am.change(am.init("W"), lambda x: x.__setitem__("t", am.Text()))
    e = EngineDocSet(backend="rows")
    e.apply_changes("doc", changes_of(d))
    served = len(changes_of(d))

    from automerge_tpu.engine.pack import ROWS_MAX_OPS
    n_rounds = 60
    budget_crossed_at = None
    total_ops = len(changes_of(d)[0].ops)
    for r in range(n_rounds):
        d = _edit_round(d, rng)
        new = changes_of(d)[served:]
        served += len(new)
        total_ops += sum(len(c.ops) for c in new)
        with e.batch():
            for c in new:
                e.apply_changes("doc", [c])
        if budget_crossed_at is None and total_ops > ROWS_MAX_OPS:
            budget_crossed_at = r
        if r % 10 == 9 or r == n_rounds - 1:
            assert np.uint32(e.hashes()["doc"]) == \
                oracle_hash(changes_of(d)), f"parity broke at round {r}"
    assert budget_crossed_at is not None and budget_crossed_at < n_rounds - 5, \
        "soak too small to cross the pre-compaction budget"
    from automerge_tpu.utils import metrics
    assert metrics.snapshot().get("rows_docs_compacted"), "soak never compacted"
    # final materialized text matches the oracle document
    assert "".join(e.materialize("doc")["data"]["t"]) == "".join(d["t"])

    # control: the bare engine with no compaction hook hits the wall
    bare = ResidentRowsDocSet(["doc"])
    with pytest.raises(RowsBudgetError):
        all_chs = changes_of(d)
        for k in range(0, len(all_chs), 64):
            bare.apply_rounds([{"doc": all_chs[k:k + 64]}])
