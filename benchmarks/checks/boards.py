"""Check `boards`: the numbers and limits of the default check (`check.py`,
every limit 0) for a fleet of card boards (`fleets/boards.py`), against a
plain reference of its own, `reference_boards.py`: Automerge's semantics over
nested maps and lists (the RGA order of a list's elements, tombstones, links)
and the canonical hash over both, in plain Python.

- `hashes_wrong` reads every board, the full boards among them: the
  reference covers every document of the fleet, so `untouched_moved` has
  nothing to read and stays 0;
- `states_wrong` reads a seeded 64 boards, the longest history, the full
  boards, and 16 boards each that the window gave concurrent inserts at one
  anchor and a duplicated card (the fleet keeps both, exactly);
- `changes_unserved` reads the full boards and a seeded tenth of the others;
  a request's changes are told apart by (board, actor, seq).

`CONTROLS`: the default four, on the board reference (`BoardService`), and
two that break a list rule: `ascending_siblings` (the elements under one
anchor in ascending order, so a later or higher-actor insert comes second)
and `tombstone_visible` (a deleted element stays in the visible sequence
with the value it last had).
"""

from __future__ import annotations

import random

import check as base
import reference
import reference_boards as rb

LIMITS = base.LIMITS
FALLBACK_COUNTERS = base.FALLBACK_COUNTERS
STATES_WIDEN = 16


class BoardService(reference.RefService):
    """The reference in the program's place, on board semantics. `broken`
    breaks a guarantee as `reference.RefService` does; `mode` breaks a list
    rule (`reference_boards.Doc`'s `siblings` / `tombstones`)."""

    def __init__(self, broken: str = "none", every: int = 97, **mode):
        super().__init__(broken, every)
        self.mode = dict(mode, first_writer=broken == "first_writer_wins")

    def _hash_of(self, doc_id: str) -> int:
        return rb.state_hash(self.logs[doc_id], **self.mode)

    def materialize(self, doc_id: str):
        self._flush()
        return rb.state(self.logs[doc_id], **self.mode)


CONTROLS = {
    **{kind: (lambda kind=kind: BoardService(kind))
       for kind in reference.BROKEN if kind != "none"},
    "ascending_siblings": lambda: BoardService(siblings="ascending"),
    "tombstone_visible": lambda: BoardService(tombstones="visible"),
}


def read_untouched(svc, fleet) -> dict:
    """Nothing: the reference covers every board."""
    return {}


def sample_docs(fleet, seed: int, n: int) -> list:
    rng = random.Random(seed ^ 0xB0A2D5)
    longest = max(fleet.small, key=lambda d: fleet.boards[d].depth)
    extra = []
    for held in (fleet.anchored, fleet.duplicated):
        held = sorted(held)
        extra += rng.sample(held, min(STATES_WIDEN, len(held)))
    return list(dict.fromkeys(
        fleet.structured + [longest]
        + rng.sample(fleet.small, min(n, len(fleet.small))) + extra))


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
    """The default's numbers and limits, on the board reference."""
    lost = {(d, *ident) for d, served in read["logs"].items()
            for ident in base.unserved(sent.get(d, ()), served)}
    hashes_wrong = sum(1 for d in fleet.doc_ids
                       if read["hashes"].get(d)
                       != rb.state_hash(sent.get(d, ())))
    states_wrong = sum(1 for d, got in read["states"].items()
                       if got != rb.state(sent.get(d, ())))
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
    return {
        "correct": all(v <= LIMITS[k] for k, v in values.items()),
        "failed": len(failed & {q.number for q in requests}),
        "compared": {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in values.items()},
        "sizes": {"changes": sum(len(sent.get(d, ())) for d in read["logs"]),
                  "hashes": len(fleet.doc_ids), "states": len(states),
                  "states_anchored": sum(1 for d in states
                                         if d in fleet.anchored),
                  "states_duplicated": sum(1 for d in states
                                           if d in fleet.duplicated),
                  "untouched": 0},
    }
