"""A batch's Change objects become the round's frame in one pass.

Inside `with svc.batch():` the rows service keeps an `apply_changes`
ingress as it came (native/wire.py ChangesPart) and the flush turns the
whole round into columns with ONE `changes_to_columns` call
(sync/frames.py round_from_parts), where it used to convert a change at
admission and join the parts at the flush. Held here: the frame is the same
bytes as the join's; an ingress that cannot be encoded, and a ghost-anchored
one, fail at their own call; a failed flush restores the round whichever
kind its parts are; everything that reads a pending part reads both kinds;
and the counter `sync_rounds_direct_frame` says which rounds took the road.
The native round converter (native/framecodec.cpp) makes the same frame and
tables as that pass, declines whatever the Python path might treat
otherwise, and `sync_rounds_native_frame` counts the rounds it made.
"""

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.engine.resident_rows import (
    CompactionAnchorError, DeviceDispatchError, RowsBudgetError)
from automerge_tpu.native.wire import (
    ChangesPart, WireColumns, changes_part, changes_to_columns)
from automerge_tpu.sync import tenantledger
from automerge_tpu.sync.frames import round_from_parts
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet
from automerge_tpu.utils import metrics

from tests.test_compaction import build_history, changes_of
from tests.test_rows_service import oracle_hash


def one_op(doc: int, seq: int = 1, value=None, key="n"):
    return [Change("storm", seq, {}, [Op("set", ROOT_ID, key=key,
                                         value=doc if value is None
                                         else value)])]


def two_writers():
    """Three changes of two actors, with messages, the last depending on
    both writers."""
    a = am.change(am.init("A"), "first", lambda d: am.assign(
        d, {"x": 1, "title": "t"}))
    b = am.change(am.merge(am.init("B"), a), "theirs",
                  lambda d: d.__setitem__("y", 2))
    return changes_of(am.change(am.merge(a, b), "merged",
                                lambda d: d.__setitem__("x", 3)))


def list_and_text():
    d = am.change(am.init("L"), lambda x: am.assign(
        x, {"xs": [1, 2, 3], "t": am.Text()}))
    d = am.change(d, lambda x: x["t"].insert_at(0, *"hello"))
    d = am.change(d, lambda x: x["xs"].append(4))
    return changes_of(am.change(d, lambda x: x["t"].delete_at(1)))


SCALARS = ["s", "", "é\ud800", 1.5, -0.0, float("inf"), True, False,
           None, 0, -1, 2**63 - 1, -(2**63), 2**63, -(2**63) - 1, 2**70]


def scalars():
    return [Change("V", 1, {}, [Op("set", ROOT_ID, key=f"k{j}", value=v)
                                for j, v in enumerate(SCALARS)])]


class Cols:
    """Marks a call of the round as `apply_columns` of these changes."""

    def __init__(self, changes):
        self.changes = changes


# {case: [(doc id, the call's changes, or Cols of them)] in call order}
ROUNDS = {
    "one-op-a-change": [(f"d{i}", one_op(i)) for i in range(40)],
    "deps-and-messages": [("w", two_writers()), ("d1", one_op(1)),
                          ("w2", two_writers()[:2])],
    "scalars": [("v", scalars()), ("d0", one_op(0, value="s")),
                ("d1", one_op(1, value=2**64))],
    "list-and-text": [("lt", list_and_text()), ("d0", one_op(0)),
                      ("lt2", list_and_text())],
    "admitted-twice": [("d0", one_op(0)), ("d1", one_op(1)),
                       ("w", two_writers()[:1]), ("d2", one_op(2)),
                       ("d0", one_op(0, seq=2, key="m")),
                       ("w", two_writers()[1:]),
                       ("d0", one_op(0, seq=3, value="z"))],
    "changes-and-columns": [("d0", one_op(0)), ("c1", Cols(one_op(1))),
                            ("d2", one_op(2)), ("d3", one_op(3)),
                            ("c1", one_op(1, seq=2)),
                            ("c4", Cols(two_writers())),
                            ("d0", Cols(one_op(0, seq=2)))],
    "an-empty-call": [("d0", one_op(0)), ("e", []), ("d1", one_op(1))],
}
DIRECT = {case: not any(isinstance(chs, Cols) for _d, chs in calls)
          for case, calls in ROUNDS.items()}


def joined(calls, a_change_at_a_time: bool):
    """The round as the join gives it: every call's changes converted at
    the call (or every change on its own), the parts joined at the
    flush."""
    parts: dict = {}
    for d, chs in calls:
        chs = chs.changes if isinstance(chs, Cols) else chs
        if a_change_at_a_time and chs:
            cols = [changes_to_columns([c]) for c in chs]
        else:
            cols = [changes_to_columns(chs)]
        parts.setdefault(d, []).extend(cols)
    return round_from_parts(parts)


def capture_rounds(svc) -> list:
    """Every RoundColumns the service hands its engine from now on."""
    seen: list = []
    rset = svc._resident
    real = rset.dispatch_round_frames

    def spy(frames, interpret=None, compactor=None):
        seen.extend(frames)
        return real(frames, interpret, compactor)

    rset.dispatch_round_frames = spy
    return seen


def send(svc, calls) -> None:
    with svc.batch():
        for d, chs in calls:
            if isinstance(chs, Cols):
                svc.apply_columns(d, changes_to_columns(chs.changes))
            else:
                svc.apply_changes(d, chs)


def all_changes(calls) -> dict:
    out: dict = {}
    for d, chs in calls:
        out.setdefault(d, []).extend(
            chs.changes if isinstance(chs, Cols) else chs)
    return out


# -- (a) the frame ----------------------------------------------------------


@pytest.mark.parametrize("case", sorted(ROUNDS))
def test_one_pass_frame_equals_the_join_byte_for_byte(case):
    calls = ROUNDS[case]
    svc = EngineDocSet(backend="rows")
    seen = capture_rounds(svc)
    send(svc, calls)
    assert len(seen) == 1
    got = seen[0]
    assert got.direct is DIRECT[case]
    for fine in (False, True):
        want = joined(calls, fine)
        assert want.direct is False
        assert got.doc_ids == want.doc_ids
        assert got.change_off.tolist() == want.change_off.tolist()
        assert got.cols.frame_bytes == want.cols.frame_bytes
    # and the engine under it converged: every document to its oracle
    hashes = svc.hashes()
    for d, chs in all_changes(calls).items():
        if chs:
            assert np.uint32(hashes[d]) == oracle_hash(chs), d


def test_unusual_but_encodable_fields_convert_at_their_call():
    """A numpy integer `elem`, a string `elem`, an int subclass for a seq:
    changes_to_columns takes them, the plain check does not know them, so
    that call's part is columns already and the frame is the same."""
    class Seq(int):
        pass

    def list_doc(seq, first, second):
        return [Change("L", seq, {}, [
            Op("makeList", "L:list"),
            Op("link", ROOT_ID, key="xs", value="L:list"),
            Op("ins", "L:list", key="_head", elem=first),
            Op("set", "L:list", key="L:1", value=7),
            Op("ins", "L:list", key="L:1", elem=second),
            Op("set", "L:list", key="L:2", value=8)])]

    odd = list_doc(Seq(1), np.int64(1), "2")
    plain = list_doc(1, 1, 2)
    assert changes_to_columns(odd).to_changes() == plain
    assert isinstance(changes_part(odd), WireColumns)
    assert isinstance(changes_part(one_op(1)), ChangesPart)
    calls = [("d0", one_op(0)), ("odd", odd), ("d1", one_op(1))]
    svc = EngineDocSet(backend="rows")
    seen = capture_rounds(svc)
    send(svc, calls)
    assert seen[0].direct is False
    assert seen[0].cols.frame_bytes == joined(calls, False).cols.frame_bytes
    assert np.uint32(svc.hashes()["odd"]) == oracle_hash(plain)


def test_the_callers_list_may_change_after_the_call():
    """The part keeps the changes, not the caller's list."""
    svc = EngineDocSet(backend="rows")
    buf = one_op(0)
    with svc.batch():
        svc.apply_changes("d0", buf)
        buf.clear()
        buf.extend(one_op(1))
        svc.apply_changes("d1", buf)
    h = svc.hashes()
    assert np.uint32(h["d0"]) == oracle_hash(one_op(0))
    assert np.uint32(h["d1"]) == oracle_hash(one_op(1))


# -- (b), (c) what cannot admit fails at its own call -----------------------


BAD = {
    "unsupported-value": (
        [Change("X", 1, {}, [Op("set", ROOT_ID, key="k", value={"a": 1})])],
        TypeError),
    "unknown-action": (
        [Change("X", 1, {}, [Op("frobnicate", ROOT_ID, key="k")])],
        KeyError),
    "bad-elem": (
        [Change("X", 1, {}, [Op("ins", "X:list", key="_head", elem="two")])],
        ValueError),
    "seq-past-int32": (
        [Change("X", 2**31, {}, [Op("set", ROOT_ID, key="k", value=1)])],
        OverflowError),
    "a-good-change-before-the-bad-one": (
        one_op(9) + [Change("X", 1, {}, [Op("set", ROOT_ID, key="k",
                                            value=b"bytes")])],
        TypeError),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_an_ingress_that_cannot_be_encoded_raises_at_its_own_call(case):
    bad, error = BAD[case]
    with pytest.raises(error):      # what the conversion itself raises
        changes_to_columns(bad)
    svc = EngineDocSet(backend="rows")
    seen = capture_rounds(svc)
    raised = None
    with svc.batch():
        svc.apply_changes("d0", one_op(0))
        try:
            svc.apply_changes("bad", bad)
        except error as e:
            raised = e
        svc.apply_changes("d1", one_op(1))
    assert raised is not None       # at the call, not at the batch's exit
    assert len(seen) == 1 and seen[0].doc_ids == ["d0", "d1"]
    assert seen[0].direct is True
    assert svc._pending == {} and "bad" not in svc.doc_ids
    h = svc.hashes()
    assert np.uint32(h["d0"]) == oracle_hash(one_op(0))
    assert np.uint32(h["d1"]) == oracle_hash(one_op(1))


class _Seq(int):
    pass


def test_a_one_shot_iterable_is_read_once_or_refused():
    """The check and the part read ONE tuple of the caller's iterable: a
    generator (no len) is refused at its call, inside a batch as outside
    one, and an iterable that can be walked only once is admitted whole,
    never acknowledged as an empty part."""
    class Once:
        def __init__(self, changes):
            self.it = iter(changes)

        def __len__(self):
            return 1

        def __iter__(self):
            return self.it

    part = changes_part(Once(one_op(3)))
    assert isinstance(part, ChangesPart)
    assert (part.n_changes, part.n_ops) == (1, 1)
    assert part.changes == tuple(one_op(3))
    odd = changes_part(Once([Change("X", _Seq(1), {}, [
        Op("set", ROOT_ID, key="k", value=5)])]))
    assert isinstance(odd, WireColumns)         # of the same one tuple
    assert (odd.n_changes, odd.n_ops) == (1, 1)
    with pytest.raises(TypeError):
        changes_part(c for c in one_op(3))

    svc = EngineDocSet(backend="rows")
    with pytest.raises(TypeError):              # as outside a batch
        svc.apply_changes("gen", (c for c in one_op(1)))
    with svc.batch():
        svc.apply_changes("d0", one_op(0))
        with pytest.raises(TypeError):
            svc.apply_changes("gen", (c for c in one_op(1)))
        svc.apply_changes("once", Once(one_op(2)))
        assert svc._pending_size() == (2, 2)
    assert "gen" not in svc.doc_ids
    h = svc.hashes()
    assert np.uint32(h["d0"]) == oracle_hash(one_op(0))
    assert np.uint32(h["once"]) == oracle_hash(one_op(2))


# every field of a change and of an op, drawn from what is plain, what
# converts though it is not plain, and what the conversion rejects
POOLS = {
    "actor": ["a", "", "é\ud800", b"a", None, 3],
    "seq": [1, 0, -1, 2**31 - 1, -(2**31), 2**31, -(2**31) - 1, True, 1.0,
            "1", None, np.int32(3), _Seq(2)],
    "message": [None, "m", "", 5, b"m"],
    "deps": [{}, {"a": 1}, {"a": 1, "b": 2**31 - 1}, {"a": 2**31}, {1: 1},
             {"a": "1"}, {"a": 1.5}, {"a": True}, {"a": None},
             {"a": np.int64(4)}, {"a": -(2**31) - 1}],
    "action": ["set", "del", "ins", "link", "move", "makeMap", "makeList",
               "makeText", "frobnicate", None, 3],
    "obj": [ROOT_ID, "x:1", "", None, 5, b"o"],
    "key": [None, "k", "", "_head", 7, b"k"],
    "elem": [None, 0, 5, -1, 2**31 - 1, 2**31, -(2**31) - 1, "2", "two",
             1.5, True, np.int64(1), _Seq(1)],
    "value": SCALARS + [float("nan"), {"a": 1}, [1], b"b", (1,),
                        np.float32(1.0), np.int64(1), _Seq(3), object()],
}
# a draw takes the first (plain) half of a pool three times in four, so
# that whole changes come out plain often enough to be kept unconverted
PLAIN_HALF = {"actor": 3, "seq": 5, "message": 3, "deps": 3, "action": 8,
              "obj": 3, "key": 4, "elem": 5, "value": len(SCALARS) + 1}


def drawn_changes(rng):
    def draw(field):
        pool = POOLS[field]
        if rng.random() < 0.9:
            pool = pool[:PLAIN_HALF[field]]
        return pool[rng.randrange(len(pool))]

    return [Change(draw("actor"), draw("seq"), draw("deps"),
                   [Op(draw("action"), draw("obj"), key=draw("key"),
                       value=draw("value"), elem=draw("elem"))
                    for _ in range(rng.randrange(4))],
                   message=draw("message"))
            for _ in range(rng.randrange(1, 4))]


@pytest.mark.parametrize("seed", range(8))
def test_what_the_plain_check_passes_the_conversion_never_rejects(seed):
    """The flush can only never raise if _plain_ops accepts a subset of
    what changes_to_columns (and the frame's serialisation after it)
    converts without raising: for random field types and ranges, a part
    kept as Change objects converts at the flush, to the op count the
    check gave; and whatever the conversion rejects, the check refused."""
    import random

    from automerge_tpu.native.wire import _plain_ops
    from automerge_tpu.sync.frames import columns_to_bytes
    rng = random.Random(seed)
    kept = rejected = 0
    for _ in range(400):
        chs = drawn_changes(rng)
        n_ops = _plain_ops(chs)
        try:
            cols = changes_to_columns(chs)
        except Exception:
            assert n_ops is None, chs
            rejected += 1
            continue
        if n_ops is None:
            continue                # odd but encodable: converted at its call
        kept += 1
        assert n_ops == cols.n_ops == sum(len(c.ops) for c in chs)
        part = changes_part(chs)
        assert type(part) is ChangesPart and part.n_ops == n_ops
        columns_to_bytes(part.columns())
    assert kept >= 40 and rejected >= 40, (kept, rejected)


def compacted_text():
    """A service holding one compacted text document, and a change that
    anchors an insert at one of its ghosted elements."""
    d = build_history()
    svc = EngineDocSet(backend="rows")
    svc.apply_changes("doc", changes_of(d))
    rset = svc._resident
    i = rset.doc_index["doc"]
    rset.compact({"doc": dict(rset.tables[i].clock)})
    assert rset.ghost_eids[i]
    text_obj = changes_of(d)[1].ops[0].obj
    bad = Change("alice", len(changes_of(d)) + 1, {}, [
        Op("ins", text_obj, key=sorted(rset.ghost_eids[i])[0], elem=999)])
    return svc, d, bad


def test_a_ghost_anchored_ingress_is_rejected_at_its_call_in_a_batch():
    svc, d, bad = compacted_text()
    rset = svc._resident
    i = rset.doc_index["doc"]
    log_before = len(rset.change_log[i])
    d2 = am.change(d, lambda x: x.__setitem__("ok", True))
    raised = False
    with svc.batch():
        svc.apply_changes("other", one_op(5))
        try:
            svc.apply_changes("doc", [bad])
        except CompactionAnchorError:
            raised = True
        # a compacted document's sound ingress is checked as the caller's
        # ops, stays unconverted for the round's one pass, and admits
        svc.apply_changes("doc", [changes_of(d2)[-1]])
        assert isinstance(svc._pending["doc"][0], ChangesPart)
        assert isinstance(svc._pending["other"][0], ChangesPart)
    assert raised
    assert len(rset.change_log[i]) == log_before + 1
    h = svc.hashes()
    assert np.uint32(h["doc"]) == oracle_hash(changes_of(d2))
    assert np.uint32(h["other"]) == oracle_hash(one_op(5))


# -- (d) restore for retry --------------------------------------------------


def undisturbed(calls) -> dict:
    svc = EngineDocSet(backend="rows")
    send(svc, calls)
    return svc.hashes()


RETRY_CALLS = ROUNDS["admitted-twice"] + [("lt", list_and_text())]


def test_a_preadmission_failure_restores_the_round_as_it_was():
    svc = EngineDocSet(backend="rows")
    rset = svc._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    real = rset.dispatch_round_frames

    def boom(frames, interpret=None, compactor=None):
        raise RuntimeError("batch would blow the VMEM budget")

    rset.dispatch_round_frames = boom
    with pytest.raises(RuntimeError, match="VMEM"):
        send(svc, RETRY_CALLS)
    rset.dispatch_round_frames = real
    # nothing admitted: every document is back, its parts unconverted and
    # in admission order
    assert list(svc._pending) == list(all_changes(RETRY_CALLS))
    assert all(type(p) is ChangesPart
               for parts in svc._pending.values() for p in parts)
    assert [p.n_changes for p in svc._pending["d0"]] == [1, 1, 1]
    seen = capture_rounds(svc)
    svc.flush()
    assert svc._pending == {}
    assert seen[0].direct is True
    assert seen[0].cols.frame_bytes == \
        joined(RETRY_CALLS, False).cols.frame_bytes
    assert svc.hashes() == undisturbed(RETRY_CALLS)


def test_a_midadmission_failure_restores_and_the_retry_admits_the_rest():
    svc = EngineDocSet(backend="rows")
    rset = svc._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback exercises a different path")
    real = rset.dispatch_round_frames
    first = [c for c in RETRY_CALLS if c[0] == "d0"]

    def partial(frames, interpret=None, compactor=None):
        # really admit d0, then fail before the rest of the round
        real([joined(first, False)])
        raise DeviceDispatchError("failed after d0",
                                  admission_complete=False)

    rset.dispatch_round_frames = partial
    ops0 = metrics.snapshot().get("sync_ops_ingested", 0)
    send(svc, RETRY_CALLS)          # swallowed: the round is restored
    rset.dispatch_round_frames = real
    assert list(svc._pending) == list(all_changes(RETRY_CALLS))
    assert all(type(p) is ChangesPart
               for parts in svc._pending.values() for p in parts)
    assert len(rset.change_log[rset.doc_index["d0"]]) == 3
    assert len(rset.change_log[rset.doc_index["lt"]]) == 0
    # nothing of the restored round was counted as ingested
    assert metrics.snapshot().get("sync_ops_ingested", 0) == ops0
    svc.flush()
    assert svc._pending == {}
    assert len(rset.change_log[rset.doc_index["d0"]]) == 3   # deduplicated
    assert svc.hashes() == undisturbed(RETRY_CALLS)


# -- (e) the readers of a pending part --------------------------------------


def test_pending_size_and_the_ledgers_count_unconverted_parts():
    calls = ROUNDS["admitted-twice"]
    sent = all_changes(calls)
    svc = EngineDocSet(backend="rows")
    tenants0 = tenantledger.ledger().section().get("admitted_total", 0)
    with svc.batch():
        for d, chs in calls:
            svc.apply_changes(d, chs)
        assert all(type(p) is ChangesPart
                   for parts in svc._pending.values() for p in parts)
        assert svc._pending_size() == (
            len(sent), sum(len(c.ops) for chs in sent.values() for c in chs))
        assert [p.n_ops for p in svc._pending["w"]] == [
            len(two_writers()[0].ops),
            sum(len(c.ops) for c in two_writers()[1:])]
    docs = svc.doc_ledger.section()["docs"]
    for d, chs in sent.items():
        assert docs[d]["admitted"] == len(chs), d
    assert tenantledger.ledger().section()["admitted_total"] - tenants0 \
        == sum(len(chs) for chs in sent.values())


def test_anchor_pins_of_unconverted_parts_under_a_budget_error():
    """A document the round takes past the resident caps compacts before
    admission with the pending round's insert anchors pinned: the pins of
    ChangesParts are those of the same ingress as columns, and the round
    then admits, dispatched once."""
    d = build_history()
    svc = EngineDocSet(backend="rows")
    svc.apply_changes("doc", changes_of(d))
    base = len(changes_of(d))
    d = am.change(d, lambda x: x["t"].insert_at(2, "X"))
    d = am.change(d, lambda x: x["t"].insert_at(5, "Y", "Z"))
    new = changes_of(d)[base:]
    want = EngineDocSet._pending_anchor_pins(
        {"doc": [changes_to_columns([c]) for c in new]})
    assert want["doc"]              # the inserts anchor at real elements

    rset = svc._resident
    real_over, real_compact = rset._over_caps, rset.compact
    real_apply = rset.dispatch_round_frames
    state = {"over": 0, "pins": None, "dispatched": 0}

    def over_once(*need):
        if not state["over"]:
            state["over"] = 1
            return np.array([rset.doc_index["doc"]])
        return real_over(*need)

    def compact(floors, pins=None):
        state["pins"] = pins
        return real_compact(floors, pins)

    def dispatched(frames, interpret=None, compactor=None):
        state["dispatched"] += 1
        return real_apply(frames, interpret, compactor)

    rset._over_caps, rset.compact = over_once, compact
    rset.dispatch_round_frames = dispatched
    with svc.batch():
        for c in new:
            svc.apply_changes("doc", [c])
        svc.apply_changes("other", one_op(1))
        assert type(svc._pending["doc"][0]) is ChangesPart
    assert state["over"] == 1 and state["pins"] == want
    assert state["dispatched"] == 1
    assert not (want["doc"] & rset.ghost_eids[rset.doc_index["doc"]])
    assert np.uint32(svc.hashes()["doc"]) == oracle_hash(changes_of(d))


# -- (f) the counter --------------------------------------------------------


def rounds(counter: str) -> int:
    """The counter summed over its `shard=` labels."""
    return sum(v for k, v in metrics.snapshot().items()
               if k.startswith(counter))


def direct_rounds() -> int:
    return rounds("sync_rounds_direct_frame")


def flushed_rounds() -> int:
    return rounds("sync_rounds_flushed")


@pytest.mark.parametrize("ingest_mode", ["epoch", "locked"])
def test_the_counter_counts_rounds_made_in_one_pass(ingest_mode):
    svc = EngineDocSet(backend="rows", ingest_mode=ingest_mode)
    try:
        d0, f0 = direct_rounds(), flushed_rounds()
        send(svc, ROUNDS["one-op-a-change"])            # Change objects
        assert (direct_rounds() - d0, flushed_rounds() - f0) == (1, 1)
        svc.apply_changes("d0", one_op(0, seq=2))       # outside a batch
        assert (direct_rounds() - d0, flushed_rounds() - f0) == (1, 2)
        with svc.batch():                               # columns only
            for i in range(3):
                svc.apply_columns(f"d{i}", changes_to_columns(
                    one_op(i, seq=5)))
        assert (direct_rounds() - d0, flushed_rounds() - f0) == (1, 3)
        send(svc, ROUNDS["changes-and-columns"])        # mixed: a join
        assert (direct_rounds() - d0, flushed_rounds() - f0) == (1, 4)
        with svc.batch():                               # one call is a round
            svc.apply_changes("d1", one_op(1, seq=7))
        assert (direct_rounds() - d0, flushed_rounds() - f0) == (2, 5)
    finally:
        svc.close()


def test_the_counter_rises_once_for_each_shard_that_flushed():
    svc = ShardedEngineDocSet(n_shards=4)
    try:
        docs = [f"d{i}" for i in range(24)]
        shards_hit = {id(svc.shard_of(d)) for d in docs}
        assert len(shards_hit) == 4
        d0 = direct_rounds()
        with svc.batch():
            for i, d in enumerate(docs):
                svc.apply_changes(d, one_op(i))
        assert direct_rounds() - d0 == 4
        snap = metrics.snapshot()
        assert all(snap.get(f"sync_rounds_direct_frame{{shard={k}}}", 0) >= 1
                   for k in range(4))
        # a round that touches one shard counts that shard alone
        only = [d for d in docs if svc.shard_of(d) is svc.shards[2]][:2]
        with svc.batch():
            for d in only:
                svc.apply_changes(d, one_op(0, seq=2))
        assert direct_rounds() - d0 == 5
        h = svc.hashes()
        assert np.uint32(h[only[0]]) == oracle_hash(
            one_op(docs.index(only[0])) + one_op(0, seq=2))
    finally:
        svc.close()


# -- (g) the native converter -----------------------------------------------


def python_frame(run):
    """The reference: the frame and tables of changes_to_columns."""
    from automerge_tpu.sync.frames import columns_to_bytes
    cols = changes_to_columns(run)
    return columns_to_bytes(cols), (cols.actors, cols.objects, cols.keys,
                                    cols.messages, cols.strings)


def assert_native_equals_python(run):
    from automerge_tpu.native.wire import changes_frame
    made = changes_frame(run)
    assert made is not None, run
    frame, tables = made
    want, want_tables = python_frame(run)
    assert frame == want
    assert tables == want_tables
    # the caller's own str objects, the first met of each
    for got_t, want_t in zip(tables[:4], want_tables[:4]):
        assert all(a is b for a, b in zip(got_t, want_t))


NATIVE_RUNS = {
    "empty": [],
    "multi-change-documents": two_writers() + list_and_text()
    + two_writers()[1:],
    "deps-and-messages": two_writers(),
    "scalars-at-the-edges": [Change("V", 1, {"W": 2**31 - 1}, [
        Op("set", ROOT_ID, key=f"k{j}", value=v)
        for j, v in enumerate(SCALARS) if v != "é\ud800"] + [
        Op("link", ROOT_ID, key="l", value="V:1"),
        Op("del", ROOT_ID, key="k0", value={"ignored": 1}),
        Op("ins", "V:list", key="_head", elem=-(2**31)),
        Op("move", "V:list", key="V:2", value="V:3", elem=2**31 - 1)],
        message="é")],
    "one-op-a-change": [c for i in range(50) for c in one_op(i)],
}


@pytest.mark.parametrize("case", sorted(NATIVE_RUNS))
def test_the_native_frame_is_the_python_frame(case):
    assert_native_equals_python(NATIVE_RUNS[case])


@pytest.mark.parametrize("seed", range(6))
def test_the_native_frame_is_the_python_frame_on_drawn_runs(seed):
    """Random runs of the plain fields (and of what is not plain): where
    the native converter answers, its frame and tables are the Python
    path's; where it declines, the Python path converts or raises."""
    import random

    from automerge_tpu.native.wire import changes_frame
    rng = random.Random(1000 + seed)
    native = declined = 0
    for _ in range(600):
        run = drawn_changes(rng)
        if changes_frame(run) is None:
            declined += 1
            continue
        native += 1
        assert_native_equals_python(run)
    assert native >= 80 and declined >= 80, (native, declined)


class _Str(str):
    pass


class _Float(float):
    pass


def _change(**field):
    """One plain change of an ins and a set, with one field replaced."""
    op_fields = {k: field.pop(k) for k in ("action", "obj", "key", "value",
                                           "elem") if k in field}
    ops = [Op("ins", "X:list", key="_head", elem=1),
           Op(op_fields.get("action", "set"), op_fields.get("obj", "X:list"),
              key=op_fields.get("key", "X:1"),
              value=op_fields.get("value", 5), elem=op_fields.get("elem"))]
    return Change(field.get("actor", "X"), field.get("seq", 1),
                  field.get("deps", {"Y": 1}), ops,
                  message=field.get("message"))


class _ChangeSub(Change):
    __slots__ = ()


class _OpSub(Op):
    __slots__ = ()


def _unset_op():
    op = Op.__new__(Op)
    op.action, op.obj, op.key, op.elem = "set", ROOT_ID, "k", None
    return Change("X", 1, {}, [op])       # no value slot: Python raises


# what the native converter declines: every case the Python path decides
DECLINED = {
    "lone-surrogate-value": _change(value="a\ud800"),
    "lone-surrogate-key": _change(key="\udfff"),
    "lone-surrogate-obj": _change(obj="o\ud800"),
    "lone-surrogate-actor": _change(actor="\ud800"),
    "lone-surrogate-dep-actor": _change(deps={"\ud800": 1}),
    "lone-surrogate-message": _change(message="m\udc00"),
    "str-subclass-actor": _change(actor=_Str("X")),
    "str-subclass-action": _change(action=_Str("set")),
    "str-subclass-value": _change(value=_Str("v")),
    "float-subclass-value": _change(value=_Float(1.5)),
    "numpy-float-value": _change(value=np.float64(2.5)),
    "int-subclass-value": _change(value=_Seq(3)),
    "bool-seq": _change(seq=True),
    "seq-past-int32": _change(seq=2**31),
    "dep-seq-past-int32": _change(deps={"Y": -(2**31) - 1}),
    "numpy-elem": _change(action="ins", elem=np.int64(2)),
    "string-elem": _change(action="ins", elem="2"),
    "elem-past-int32": _change(action="ins", elem=2**31),
    "change-subclass": _ChangeSub("X", 1, {}, [Op("set", ROOT_ID, key="k",
                                                  value=1)]),
    "op-subclass": Change("X", 1, {}, [_OpSub("set", ROOT_ID, key="k",
                                              value=1)]),
    "unknown-action": _change(action="frobnicate"),
    "unsupported-value": _change(value={"a": 1}),
    "unset-slot": _unset_op(),
}


@pytest.mark.parametrize("case", sorted(DECLINED))
def test_what_the_native_converter_declines_the_python_path_decides(
        case, monkeypatch):
    """A run holding the odd change is declined whole, and the round falls
    back to changes_to_columns: the same frame as a round made in Python
    alone, or the same error."""
    from automerge_tpu.native.wire import ChangesPart, changes_frame
    from automerge_tpu.sync import frames
    run = one_op(0) + [DECLINED[case]] + one_op(1, seq=2)
    assert changes_frame(run) is None
    parts = {"d0": [ChangesPart(tuple(run[:1]), 1)],
             "odd": [ChangesPart(tuple(run[1:2]), len(run[1].ops))],
             "d1": [ChangesPart(tuple(run[2:]), 1)]}
    try:
        want = python_frame(run)[0]
    except Exception as e:          # noqa: BLE001 - the reference decides
        with pytest.raises(type(e)):
            round_from_parts(parts)
        return
    got = round_from_parts(parts)
    assert (got.direct, got.native) == (True, False)
    assert got.cols.frame_bytes == want
    monkeypatch.setattr(frames, "changes_frame", lambda run: None)
    assert round_from_parts(parts).cols.frame_bytes == want


def test_columns_between_runs_join_the_native_runs_as_the_python_runs(
        monkeypatch):
    """Column parts between runs of ChangesParts: each run converted
    natively, then joined; the frame is the one a round made in Python
    alone gives, and such a round is neither direct nor native."""
    from automerge_tpu.sync import frames
    parts: dict = {}
    for d, chs in ROUNDS["changes-and-columns"] + [("lt", list_and_text())]:
        part = (changes_to_columns(chs.changes) if isinstance(chs, Cols)
                else changes_part(chs))
        parts.setdefault(d, []).append(part)
    assert any(type(p) is ChangesPart for ps in parts.values() for p in ps)
    got = round_from_parts(parts)
    monkeypatch.setattr(frames, "changes_frame", lambda run: None)
    want = round_from_parts(parts)
    assert (got.direct, got.native, want.native) == (False, False, False)
    assert got.doc_ids == want.doc_ids
    assert got.change_off.tolist() == want.change_off.tolist()
    assert got.cols.frame_bytes == want.cols.frame_bytes


def test_a_native_round_reads_as_the_python_round():
    """The native round's columns are views of its own frame: every column
    and table equal to the Python path's, the frame its bytes."""
    from automerge_tpu.native.wire import ChangesPart
    calls = ROUNDS["deps-and-messages"] + ROUNDS["list-and-text"]
    parts: dict = {}
    for d, chs in calls:
        parts.setdefault(d, []).append(ChangesPart(tuple(chs), sum(
            len(c.ops) for c in chs)))
    got = round_from_parts(parts)
    assert (got.direct, got.native) == (True, True)
    want = changes_to_columns([c for _d, chs in calls for c in chs])
    for field in ("change_actor", "change_seq", "change_msg", "deps_off",
                  "deps_actor", "deps_seq", "op_off", "op_action", "op_obj",
                  "op_key", "op_elem", "op_vtag", "op_vint", "op_vdbl",
                  "op_vstr", "actors", "objects", "keys", "messages",
                  "strings"):
        a, b = getattr(got.cols, field), getattr(want, field)
        assert np.array_equal(np.asarray(a), np.asarray(b)), field
    assert got.cols.to_changes() == want.to_changes()


def native_rounds() -> int:
    return rounds("sync_rounds_native_frame")


def test_a_batch_round_counts_as_native_and_direct_once(monkeypatch):
    """A batch() round of Change objects bumps the native and the
    direct-frame counters once each; the same round with the converter
    stubbed out bumps the direct one alone, and both services hold the
    same hashes."""
    from automerge_tpu.sync import frames
    calls = ROUNDS["deps-and-messages"] + ROUNDS["list-and-text"] + \
        ROUNDS["one-op-a-change"]
    svc = EngineDocSet(backend="rows")
    n0, d0 = native_rounds(), direct_rounds()
    send(svc, calls)
    assert (native_rounds() - n0, direct_rounds() - d0) == (1, 1)

    stubbed = EngineDocSet(backend="rows")
    monkeypatch.setattr(frames, "changes_frame", lambda run: None)
    n0, d0 = native_rounds(), direct_rounds()
    send(stubbed, calls)
    assert (native_rounds() - n0, direct_rounds() - d0) == (0, 1)
    assert stubbed.hashes() == svc.hashes()
    for d, chs in all_changes(calls).items():
        assert np.uint32(svc.hashes()[d]) == oracle_hash(chs), d


def test_the_build_names_a_missing_python_header(monkeypatch, tmp_path):
    """Where the converter does not build for want of the interpreter's C
    headers, the build's error says so."""
    import subprocess

    from automerge_tpu import native

    class Failed:
        returncode = 1
        stderr = ("framecodec.cpp:25:10: fatal error: Python.h: No such "
                  "file or directory")

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Failed())
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path))
    err = native._build_shared("framecodec.cpp",
                               str(tmp_path / "libx.so"), python_api=True)
    assert err.startswith("compile failed: Python.h not found in ")
    plain = native._build_shared("wirecodec.cpp", str(tmp_path / "liby.so"))
    assert "Python.h not found" not in plain
