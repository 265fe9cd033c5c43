"""Check `lists`: the numbers and limits of the default check (`check.py`,
every limit 0) for a fleet of long-lived lists (`fleets/lists.py`), against
the plain RGA of `reference_boards.py` (a list of strings under the root is a
board without nesting) and the card-board check's reference service
(`checks/boards.py`), both by import.

- `hashes_wrong` reads every document, the full ones among them;
- `states_wrong` reads a seeded 64 lists, the 8 longest histories, every
  list whose history passed the resident op rows (each of them compacted in
  the window or its warm-up: a list holds no more rows than that), the full
  documents, and 16 lists each that the window gave concurrent inserts at
  one anchor and an insert anchored at an element the change it had not
  seen deleted (the fleet keeps both, exactly);
- `changes_unserved` reads the full documents and a seeded tenth of the
  lists; a request's changes are told apart by (list, actor, seq).

`CONTROLS`: the default four, on the board reference, and
`reclaim_above_floor`: a reference that drops a tombstone which an insert
concurrent with its deletion still anchors at, and with it what hangs under
it, as a compaction that reclaimed above the floor would.
"""

from __future__ import annotations

import importlib.util
import os
import random
import sys

import check as base
import reference
import reference_boards as rb

LIMITS = base.LIMITS
FALLBACK_COUNTERS = base.FALLBACK_COUNTERS
STATES_WIDEN = 16
LONGEST = 8


def _sibling(name: str):
    """`checks/<name>.py` beside this file, as `run.load_by_path` names it."""
    key = f"bench_checks_{name}"
    mod = sys.modules.get(key)
    if mod is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            name + ".py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


BoardService = _sibling("boards").BoardService


class ReclaimedDoc(rb.Doc):
    """The RGA with one rule broken: a deleted element under which an
    element was inserted concurrently with the deletion (neither change
    had seen the other) is dropped from its list, and its subtree with
    it."""

    def __init__(self, changes, **mode):
        # (list, eid) -> [(actor, seq, clock seen)] of the inserts anchored
        # at the element, and of its deletions
        self.anchors: dict = {}
        self.dels: dict = {}
        super().__init__(changes, **mode)

    def _apply(self, c, op, saw: dict, first_writer: bool) -> None:
        super()._apply(c, op, saw, first_writer)
        if op.action in ("ins", "del"):
            held = self.anchors if op.action == "ins" else self.dels
            held.setdefault((op.obj, op.key), []).append(
                (c.actor, c.seq, saw))

    def order(self, obj: str) -> list:
        got = self._order.get(obj)
        if got is not None:
            return got
        dropped = {eid for (o, eid), dels in self.dels.items()
                   if o == obj and not self.candidates(obj, eid)
                   and any(concurrent(i, d)
                           for i in self.anchors.get((obj, eid), ())
                           for d in dels)}
        under: dict = {}
        for eid, (anchor, elem, actor) in self.elems.get(obj, {}).items():
            if eid not in dropped:
                under.setdefault(anchor, []).append(((elem, actor), eid))
        for kids in under.values():
            kids.sort(reverse=True)
        out = []
        stack = list(reversed(under.get(rb.HEAD, ())))
        while stack:
            _, eid = stack.pop()
            out.append(eid)
            stack.extend(reversed(under.get(eid, ())))
        self._order[obj] = out
        return out


def concurrent(one: tuple, other: tuple) -> bool:
    """Whether two ops' changes, (actor, seq, clock seen), are distinct
    and neither had seen the other."""
    (a1, s1, saw1), (a2, s2, saw2) = one, other
    return (a1, s1) != (a2, s2) and saw1.get(a2, 0) < s2 \
        and saw2.get(a1, 0) < s1


class ReclaimService(BoardService):
    """The reference in the program's place, reclaiming above the floor."""

    def _hash_of(self, doc_id: str) -> int:
        return ReclaimedDoc(self.logs[doc_id]).state_hash()

    def materialize(self, doc_id: str):
        self._flush()
        return ReclaimedDoc(self.logs[doc_id]).state()


CONTROLS = {
    **{kind: (lambda kind=kind: BoardService(kind))
       for kind in reference.BROKEN if kind != "none"},
    "reclaim_above_floor": ReclaimService,
}


def read_untouched(svc, fleet) -> dict:
    """Nothing: the reference covers every document."""
    return {}


def compacted(fleet) -> list:
    """Lists whose history passed the resident op rows: the engine
    compacted each of them before it could."""
    cap = fleet.spec.history_cap
    return [d for d in fleet.small if fleet.lists[d].depth > cap]


def sample_docs(fleet, seed: int, n: int) -> list:
    rng = random.Random(seed ^ 0x11575)
    longest = sorted(fleet.small, key=lambda d: -fleet.lists[d].depth)
    extra = []
    for held in (fleet.anchored, fleet.reanchored):
        held = sorted(held)
        extra += rng.sample(held, min(STATES_WIDEN, len(held)))
    return list(dict.fromkeys(
        fleet.structured + longest[:LONGEST]
        + rng.sample(fleet.small, min(n, len(fleet.small)))
        + compacted(fleet) + extra))


def log_docs(fleet, seed: int) -> list:
    rng = random.Random(seed ^ 0x1065)
    return fleet.structured + rng.sample(
        fleet.small, max(1, len(fleet.small) // 10))


def read_program(svc, fleet, seed: int, n_sample: int) -> dict:
    answer = base._answer
    return {
        "hashes": svc.hashes(),
        "logs": {d: answer(lambda d: list(svc.missing_changes(d, {})), d)
                 for d in log_docs(fleet, seed)},
        "states": {d: answer(svc.materialize, d)
                   for d in sample_docs(fleet, seed, n_sample)},
        "untouched": {},
    }


def decide(read: dict, fleet, sent: dict, origin: dict,
           untouched_before: dict, requests: list, fallbacks: int) -> dict:
    """The default's numbers and limits, on the list reference."""
    lost = {(d, *ident) for d, served in read["logs"].items()
            for ident in base.unserved(sent.get(d, ()), served)}
    hashes_wrong = states_wrong = 0
    for d in fleet.doc_ids:
        # one reference document serves both comparisons
        ref = rb.Doc(sent.get(d, ()))
        hashes_wrong += read["hashes"].get(d) != ref.state_hash()
        if d in read["states"]:
            states_wrong += read["states"][d] != ref.state()
    failed = {q.number for q in requests if q.error is not None}
    failed |= {origin[ident] for ident in lost if ident in origin}
    values = {
        "requests_raised": sum(1 for q in requests if q.error is not None),
        "acks_before_flush": sum(1 for q in requests
                                 if q.error is None and not q.flushed),
        "changes_unserved": len(lost),
        "hashes_wrong": hashes_wrong,
        "states_wrong": states_wrong,
        "untouched_moved": 0,
        "host_fallbacks": int(fallbacks),
    }
    states = read["states"]
    past = set(compacted(fleet))
    return {
        "correct": all(v <= LIMITS[k] for k, v in values.items()),
        "failed": len(failed & {q.number for q in requests}),
        "compared": {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in values.items()},
        "sizes": {"changes": sum(len(sent.get(d, ())) for d in read["logs"]),
                  "hashes": len(fleet.doc_ids), "states": len(states),
                  "states_compacted": sum(1 for d in states if d in past),
                  "states_anchored": sum(1 for d in states
                                         if d in fleet.anchored),
                  "states_reanchored": sum(1 for d in states
                                           if d in fleet.reanchored),
                  "untouched": 0},
    }
