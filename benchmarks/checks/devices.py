"""Check `devices`: the default check (`check.py`: its numbers, its limits,
every one 0, and its plain reference, `reference.py`, which already holds
Automerge's map semantics for concurrent writers: causal order, survivors,
the winner by actor id, the canonical hash over actor content) for a fleet
whose documents are written by their own devices (`fleets/devices.py`).

Two things are its own:

- the sample of materialized states is widened by 16 documents that ended
  the window holding a conflict and 16 that a device joined in it (the
  fleet keeps both, exactly), so that `states_wrong`, in which a conflict
  counts as state, reads documents where the order of actor ids decides;
- one control beside the default's four, `lowest_actor_wins`: the reference
  in the program's place with the LWW order reversed (the lowest actor id
  wins a key and the others are its conflicts). The state hash sums over
  every surviving `set` whoever wins, so this control is caught by
  `states_wrong` alone, and only on documents that hold a conflict.

A request's changes are told apart by (document, actor, seq): two devices
of one document share sequence numbers.
"""

from __future__ import annotations

import random

import check as base
import reference

LIMITS = base.LIMITS
FALLBACK_COUNTERS = base.FALLBACK_COUNTERS
read_untouched = base.read_untouched
WIDEN = 16


class LowestActorWins(reference.RefService):
    """The reference in the program's place, sound but for the LWW order:
    the surviving `set` of the LOWEST actor id wins a key."""

    def materialize(self, doc_id: str):
        self._flush()
        log = self.logs[doc_id]
        if not reference.covers(log):
            return super().materialize(doc_id)
        data, conflicts = {}, {}
        for key, sets in reference.survivors(log).items():
            sets = sorted(sets, key=lambda o: o[0])
            data[key] = sets[0][2]
            if len(sets) > 1:
                conflicts[key] = {a: v for a, _s, v in sets[1:]}
        return {"data": data, "conflicts": conflicts}


CONTROLS = {**base.CONTROLS, "lowest_actor_wins": LowestActorWins}


def sample_docs(fleet, seed: int, n: int) -> list:
    """The default sample, and `WIDEN` documents each of those that hold a
    conflict now and those a device joined since the load (fewer where the
    fleet has fewer)."""
    rng = random.Random(seed ^ 0xDE71CE)
    conflicted = sorted(d for d, keys in fleet.conflicted.items() if keys)
    joined = sorted(fleet.joined)
    extra = rng.sample(conflicted, min(WIDEN, len(conflicted))) \
        + rng.sample(joined, min(WIDEN, len(joined)))
    return list(dict.fromkeys(base.sample_docs(fleet, seed, n) + extra))


def read_program(svc, fleet, seed: int, n_sample: int) -> dict:
    read_ = base._answer
    return {
        "hashes": svc.hashes(),
        "logs": {d: read_(lambda d: list(svc.missing_changes(d, {})), d)
                 for d in base.log_docs(fleet, seed)},
        "states": {d: read_(svc.materialize, d)
                   for d in sample_docs(fleet, seed, n_sample)},
        "untouched": read_untouched(svc, fleet),
    }


def decide(read: dict, fleet, sent: dict, origin: dict,
           untouched_before: dict, requests: list, fallbacks: int) -> dict:
    """The default's numbers and limits; the requests that failed are
    found by (document, actor, seq), as this fleet's `replay` keys them."""
    verdict = base.decide(read, fleet, sent, {}, untouched_before, requests,
                          fallbacks)
    failed = {q.number for q in requests if q.error is not None}
    for d, served in read["logs"].items():
        for ident in base.unserved(sent.get(d, ()), served):
            if (d, *ident) in origin:
                failed.add(origin[(d, *ident)])
    verdict["failed"] = len(failed & {q.number for q in requests})
    # of the states compared: documents that hold a conflict, and that a
    # device joined
    verdict["sizes"]["states_conflicted"] = sum(
        1 for d in read["states"] if fleet.conflicted.get(d))
    verdict["sizes"]["states_joined"] = sum(
        1 for d in read["states"] if d in fleet.joined)
    return verdict
