"""List inserts placed against the host mirror's positions
(ResidentRowsDocSet._placed_pos_rows) against the full re-linearization.

A round's inserts that are each their list's newest element are placed
after their anchors in the positions the mirror's `ip` band already holds;
every other list is re-linearized from its ins log. After every round the
`ip` band must equal, cell for cell, what `_linearized_pos_rows` derives
from the ins logs of every list: over seeded multi-writer rounds (chains of
inserts in one change, head inserts, two lists in one document, concurrent
inserts at one anchor), across a compaction that leaves ghosts, and across
a registration that permutes the writers' ranks.
"""

import random

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.core.change import Change, Op
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import metrics

from tests.test_rows_service import oracle_hash

ROOT = "00000000-0000-0000-0000-000000000000"


def counter(name: str) -> int:
    return int(metrics.snapshot().get(name, 0))


def assert_positions_are_the_linearization(rset):
    lists = [(i, lrow) for i in range(len(rset.doc_ids))
             for lrow in rset.ins_log[i]]
    docs, rows, pos = rset._linearized_pos_rows(lists)
    np.testing.assert_array_equal(rset.rows_host[rows, docs], pos)


def changes_of(doc):
    return doc._doc.opset.get_missing_changes({})


class Writers:
    """Replicas of one document that edit its lists and meet now and then;
    `take()` hands the changes the service has not had yet. A writer edits
    as it joins: the service's causal floor then bounds what it has seen,
    as a peer's advertised clock would."""

    def __init__(self, rng, names, lists=("a", "b")):
        self.rng = rng
        self.lists = lists
        base = am.change(am.init(names[0]), lambda d: [
            d.__setitem__(k, list("xyz")) for k in lists])
        self.reps = {names[0]: base}
        self.sent = set()
        for n in names[1:]:
            self.reps[n] = am.merge(am.init(n), base)
            self.edit(n)

    def join(self, name):
        """A writer new to the document, from everything so far."""
        self.reps[name] = am.merge(am.init(name), self.merged())
        self.edit(name)

    def merged(self):
        out = am.init("0" * 32)          # an observer: it writes nothing
        for n in sorted(self.reps):
            out = am.merge(out, self.reps[n])
        return out

    def sync(self):
        m = self.merged()
        for n in self.reps:
            self.reps[n] = am.merge(self.reps[n], m)

    def edit(self, name):
        rng = self.rng
        key = rng.choice(self.lists)

        def fn(d):
            lst = d[key]
            for _ in range(rng.randint(1, 4)):
                n = len(lst)
                r = rng.random()
                if r < 0.2 or n == 0:
                    lst.insert_at(0, *"h" * rng.randint(1, 3))
                elif r < 0.65:
                    lst.insert_at(rng.randint(0, n),
                                  *"c" * rng.randint(1, 4))
                elif n > 1:
                    lst.delete_at(rng.randint(0, n - 1))
        self.reps[name] = am.change(self.reps[name], fn)

    def take(self):
        out = []
        for c in changes_of(self.merged()):
            if (c.actor, c.seq) not in self.sent:
                self.sent.add((c.actor, c.seq))
                out.append(c)
        return out


def _round(svc, docs):
    with svc.batch():
        for doc, w in docs.items():
            chs = w.take()
            if chs:
                svc.apply_changes(doc, chs)
    assert_positions_are_the_linearization(svc._resident)


def _edit(rng, w):
    names = sorted(w.reps)
    if rng.random() < 0.7:
        w.sync()
        w.edit(rng.choice(names))
    else:
        for n in rng.sample(names, 2):
            w.edit(n)


@pytest.mark.parametrize("seed", range(4))
def test_the_mirror_positions_are_the_full_linearization(seed):
    """Seeded rounds over two documents, one of two lists and one of one
    list, each round a change or two to each: mostly by a writer that has
    seen everything, now and then by two that have not seen each other's
    last edits (concurrent inserts, equal and lower counters at one
    anchor). The one-list document is compacted to its whole clock,
    leaving ghosts, and inserted into after; a writer whose name sorts
    first joins both and permutes the ranks."""
    rng = random.Random(1000 + seed)
    docs = {"two": Writers(rng, ["m" * 32, "t" * 32], lists=("a", "b")),
            "one": Writers(rng, ["m" * 32, "t" * 32], lists=("a",))}
    svc = EngineDocSet(backend="rows")
    # caps the rounds never reach: no compaction but the one below (a
    # document's ghosts are kept by element id alone, not by list)
    svc._resident.reserve(ops_per_doc=512, elems_per_list=256,
                          lists_per_doc=2)
    placed0 = counter("rows_elem_lists_placed")
    relin0 = counter("rows_elem_lists_relinearized")
    try:
        for r in range(36):
            if r == 12:
                for w in docs.values():
                    w.join("c" * 32)     # sorts before both: ranks move
            if r == 20:
                docs["one"].sync()
                _round(svc, docs)
                rset = svc._resident
                i = rset.doc_index["one"]
                stats = rset.compact(
                    {"one": dict(rset.tables[i].clock)})["one"]
                assert stats["elems_after"] < stats["elems_before"]
                assert any(s < 0 for e in rset.ins_log[i].values()
                           for (s, _, _, _) in e)
                assert_positions_are_the_linearization(rset)
            for w in docs.values():
                _edit(rng, w)
            _round(svc, docs)
        hashes = svc.hashes()
        for doc, w in docs.items():
            want = w.merged()
            assert np.uint32(hashes[doc]) == oracle_hash(changes_of(want))
            got = svc.materialize(doc)["data"]
            assert {k: list(got[k]) for k in w.lists} == \
                {k: list(want[k]) for k in w.lists}
    finally:
        svc.close()
    assert counter("rows_elem_lists_placed") > placed0
    assert counter("rows_elem_lists_relinearized") > relin0


def _list_doc():
    """One writer's list of three elements: (changes, actor, list id)."""
    a, obj = "a" * 32, "list"
    ops = [Op("makeList", obj), Op("link", ROOT, key="items", value=obj)]
    prev = "_head"
    for k in range(1, 4):
        ops += [Op("ins", obj, key=prev, elem=k),
                Op("set", obj, key=f"{a}:{k}", value=f"v{k}")]
        prev = f"{a}:{k}"
    return [Change(a, 1, {}, ops)], a, obj


def _ins(actor, seq, deps, obj, anchor, elems):
    """A change inserting a chain of elements after `anchor`."""
    ops = []
    for e in elems:
        ops += [Op("ins", obj, key=anchor, elem=e),
                Op("set", obj, key=f"{actor}:{e}", value=f"{actor[0]}{e}")]
        anchor = f"{actor}:{e}"
    return Change(actor, seq, deps, ops)


@pytest.mark.parametrize("elem", [4, 5, 3], ids=["equal", "higher", "lower"])
def test_a_concurrent_sibling_takes_the_full_linearization(elem):
    """B's insert at `A:1` concurrent with A's there: equal, higher or
    lower than the counter A's insert took. Only the higher one is the
    list's newest element and is placed; the others re-linearize."""
    chs, a, obj = _list_doc()
    b = "b" * 32
    svc = EngineDocSet(backend="rows")
    try:
        svc.apply_changes("doc", chs)
        svc.apply_changes("doc", [_ins(a, 2, {}, obj, f"{a}:1", [4])])
        placed = counter("rows_elem_lists_placed")
        relin = counter("rows_elem_lists_relinearized")
        theirs = _ins(b, 1, {a: 1}, obj, f"{a}:1", [elem])
        svc.apply_changes("doc", [theirs])
        assert_positions_are_the_linearization(svc._resident)
        concurrent = elem <= 4
        assert counter("rows_elem_lists_relinearized") - relin == concurrent
        assert counter("rows_elem_lists_placed") - placed == (not concurrent)
        log = chs + [_ins(a, 2, {}, obj, f"{a}:1", [4]), theirs]
        assert np.uint32(svc.hashes()["doc"]) == oracle_hash(log)
    finally:
        svc.close()


def test_a_chain_at_the_head_ships_only_what_moved():
    """A chain of three inserts at the head, then one at the tail: the
    chain moves every element (three new slots and three shifted), the tail
    insert moves nothing but its own slot."""
    chs, a, obj = _list_doc()
    svc = EngineDocSet(backend="rows")
    try:
        svc.apply_changes("doc", chs)
        shipped = counter("rows_elem_pos_rows_shipped")
        svc.apply_changes("doc", [_ins(a, 2, {}, obj, "_head", [4, 5, 6])])
        assert counter("rows_elem_pos_rows_shipped") - shipped == 6
        shipped = counter("rows_elem_pos_rows_shipped")
        svc.apply_changes("doc", [_ins(a, 3, {}, obj, f"{a}:3", [7])])
        assert counter("rows_elem_pos_rows_shipped") - shipped == 1
        assert_positions_are_the_linearization(svc._resident)
        assert svc.materialize("doc")["data"]["items"] == [
            "a4", "a5", "a6", "v1", "v2", "v3", "a7"]
    finally:
        svc.close()
