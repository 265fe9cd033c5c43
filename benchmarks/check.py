"""The comparison that decides `correct`: what the timed window left
resident in the service, at the timed size, against the plain reference
(`reference.py`) computed from the changes the benchmark itself sent.

The changes are not kept while the window runs: `fleet.replay` makes them
again from the seed once it has closed. Every number compared is a count
with the limit 0 (exact comparisons):

- `requests_raised`: requests of the window whose call raised;
- `acks_before_flush`: requests of the window on whose return the service
  had not yet flushed their ops through its engine (the driver reads the
  service's count of ingested ops after each return, outside the timed
  span): the first guarantee, that the acknowledging call returns only
  after the change is flushed;
- `changes_unserved`: acknowledged changes, load and window, that
  `missing_changes(doc, {})` does not serve back with the same ops; read
  for every structured document and a seeded tenth of the small ones
  (reading back every log costs the service as long as the window);
- `hashes_wrong`: map documents whose resident hash, as the window's own
  flushes left it, differs from the reference's hash of their log;
- `states_wrong`: documents of a seeded sample (with the longest history
  and every heavy document in it) whose `materialize` differs from the
  reference's state;
- `untouched_moved`: list, text and move documents, which take no traffic,
  whose hash or state differs from what it was before the warm-up;
- `host_fallbacks`: the engine acknowledged from host truth after a device
  dispatch failed (`rows_dispatch_failed`, `rows_log_rebuilt`,
  `rows_engine_poisoned`), over the whole run.

This module is also the check a configuration gets by naming none: what
`run.py` takes from it (`read_untouched`, `read_program`, `decide`,
`FALLBACK_COUNTERS`) and what `prove.py` takes (`CONTROLS`) is what a
check of a configuration's own gives (`checks/<name>.py`; README, "The
three seams").
"""

from __future__ import annotations

import random

import reference

LIMITS = {"requests_raised": 0, "acks_before_flush": 0,
          "changes_unserved": 0, "hashes_wrong": 0, "states_wrong": 0,
          "untouched_moved": 0, "host_fallbacks": 0}
FALLBACK_COUNTERS = ("rows_dispatch_failed", "rows_log_rebuilt",
                     "rows_engine_poisoned")
# the controls `prove.py --control 1` runs: for each guarantee that can be
# broken, what makes the reference that stands in the program's place
CONTROLS = {kind: (lambda kind=kind: reference.RefService(kind))
            for kind in reference.BROKEN if kind != "none"}


def sample_docs(fleet, seed: int, n: int) -> list:
    """The documents whose materialized state is compared: every covered
    structured document, the small document with the longest history, and
    a seeded draw of `n` small documents."""
    rng = random.Random(seed ^ 0x5EED)
    covered = [d for d in fleet.structured
               if reference.covers(fleet.first[d])]
    longest = max(fleet.small, key=lambda d: fleet.depth.get(d, 0))
    rest = rng.sample(fleet.small, min(n, len(fleet.small)))
    return list(dict.fromkeys(covered + [longest] + rest))


def _answer(call, *args):
    """What the service answers, or that it raised: a document it does not
    know is a wrong answer, not the end of the run."""
    try:
        return call(*args)
    except Exception as e:
        return ("raised", repr(e)[:200])


def read_untouched(svc, fleet) -> dict:
    """Hash and state of the documents the reference does not cover."""
    docs = [d for d in fleet.structured
            if not reference.covers(fleet.first[d])]
    hashes = svc.hashes()
    return {d: (hashes.get(d), _answer(svc.materialize, d)) for d in docs}


def log_docs(fleet, seed: int) -> list:
    rng = random.Random(seed ^ 0x1065)
    return fleet.structured + rng.sample(
        fleet.small, max(1, len(fleet.small) // 10))


def read_program(svc, fleet, seed: int, n_sample: int) -> dict:
    """Everything the comparison needs from the service, read once the
    window has closed; after this the service can be closed and freed."""
    return {
        "hashes": svc.hashes(),
        "logs": {d: _answer(lambda d: list(svc.missing_changes(d, {})), d)
                 for d in log_docs(fleet, seed)},
        "states": {d: _answer(svc.materialize, d)
                   for d in sample_docs(fleet, seed, n_sample)},
        "untouched": read_untouched(svc, fleet),
    }


def _ops(change) -> list:
    return [(o.action, o.obj, o.key, o.value) for o in change.ops]


def unserved(acked: list, served) -> list:
    """The (actor, seq) of acknowledged changes not served back as sent."""
    if isinstance(served, tuple):      # the read raised
        served = ()
    got = {(c.actor, c.seq): c for c in served}
    lost = []
    for c in acked:
        s = got.get((c.actor, c.seq))
        if s is None or _ops(s) != _ops(c):
            lost.append((c.actor, c.seq))
    return lost


def decide(read: dict, fleet, sent: dict, origin: dict,
           untouched_before: dict, requests: list, fallbacks: int) -> dict:
    """The numbers compared, each beside its limit, `correct`, and the
    requests that failed (raised, or sent a change that is not served).
    `sent` is every acknowledged change by document and `origin` the
    request each window change came with (`fleet.replay`)."""
    lost = {(d, *ident) for d, served in read["logs"].items()
            for ident in unserved(sent.get(d, ()), served)}
    hashes_wrong = 0
    n_hashed = 0
    for d in fleet.doc_ids:
        log = sent.get(d, ())
        if not reference.covers(log):
            continue
        n_hashed += 1
        if read["hashes"].get(d) != reference.state_hash(log):
            hashes_wrong += 1
    states_wrong = sum(
        1 for d, got in read["states"].items()
        if got != reference.state(sent.get(d, ())))
    moved = sum(1 for d, before in untouched_before.items()
                if read["untouched"].get(d) != before)
    failed = {q.number for q in requests if q.error is not None}
    failed |= {origin[(d, seq)] for d, _actor, seq in lost
               if (d, seq) in origin}
    values = {
        "requests_raised": sum(1 for q in requests if q.error is not None),
        "acks_before_flush": sum(1 for q in requests
                                 if q.error is None and not q.flushed),
        "changes_unserved": len(lost),
        "hashes_wrong": hashes_wrong,
        "states_wrong": states_wrong,
        "untouched_moved": moved,
        "host_fallbacks": int(fallbacks),
    }
    compared = {k: {"value": v, "limit": LIMITS[k]}
                for k, v in values.items()}
    return {
        "correct": all(v <= LIMITS[k] for k, v in values.items()),
        "failed": len(failed & {q.number for q in requests}),
        "compared": compared,
        "sizes": {"changes": sum(len(sent.get(d, ()))
                                 for d in read["logs"]),
                  "hashes": n_hashed, "states": len(read["states"]),
                  "untouched": len(untouched_before)},
    }
