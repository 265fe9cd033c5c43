"""Failure-injection soak for the round-4 recovery machinery: a rows sync
node ingesting a long random concurrent trace while device dispatches fail
at random points must end bit-identical to a never-failed node and to the
interpretive oracle — admission must be exactly-once (no drops, no double
applies) across dispatch failures, readback failures, and mid-admission
rebuilds."""

import random

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.engine.resident_rows import DeviceDispatchError
from automerge_tpu.sync.service import EngineDocSet

from tests.test_rows_service import oracle_hash


def _trace(rng, n_docs=12, n_rounds=10):
    """Random concurrent 2-actor edits over n_docs docs; yields per-round
    {doc_id: delta} dicts and returns final per-doc full change sets."""
    docs = {}
    for i in range(n_docs):
        a = am.change(am.init("A"), lambda d, i=i: am.assign(
            d, {"n": i, "xs": [i], "t": am.Text()}))
        docs[f"d{i}"] = (a, am.merge(am.init("B"), a))
    # round 0 ships every doc's base state; later rounds are deltas
    rounds = [{did: am.merge(a, b)._doc.opset.get_missing_changes({})
               for did, (a, b) in docs.items()}]
    for rnd in range(n_rounds):
        deltas = {}
        for did in rng.sample(list(docs), rng.randint(1, n_docs)):
            a, b = docs[did]
            which = rng.random()
            if which < 0.4:
                a2 = am.change(a, lambda d, r=rnd: d.__setitem__("n", r))
                b2 = b
            elif which < 0.7:
                b2 = am.change(b, lambda d, r=rnd: d["xs"].append(r))
                a2 = a
            else:
                a2 = am.change(a, lambda d: d["t"].insert_at(
                    0, rng.choice("xyz")))
                b2 = b
            m = am.merge(a2, b2)
            m2 = am.merge(b2, a2)
            old_clock = dict(am.merge(a, b)._doc.opset.clock)
            deltas[did] = m._doc.opset.get_missing_changes(old_clock)
            docs[did] = (m, m2)
        if deltas:
            rounds.append(deltas)
    finals = {did: am.merge(a, b)._doc.opset.get_missing_changes({})
              for did, (a, b) in docs.items()}
    return rounds, finals


@pytest.mark.parametrize("seed", [11, 22, 33])
def test_soak_random_dispatch_failures_converge(seed, monkeypatch):
    rng = random.Random(seed)
    rounds, finals = _trace(rng)

    e = EngineDocSet(backend="rows")
    rset = e._resident
    if rset._native is None:
        pytest.skip("python-encoder fallback has no dispatch stage")
    # this soak targets the DISPATCH failure classes (the TPU posture:
    # eager per-flush dispatch + cached hash handles); pin lazy off so the
    # CPU service default doesn't bypass the machinery under test, and pin
    # megabatch off so the fused round route (r20 — host-mirror
    # authoritative, no cached flush-time handle) doesn't bypass the
    # handle readback under test (the fused route's own failure soak
    # lives in tests/test_megabatch.py)
    from automerge_tpu.engine import dispatch as round_dispatch
    monkeypatch.setattr(round_dispatch, "_megabatch", False)
    rset.lazy_dispatch = False
    e._lazy_resolved = True
    for did in finals:
        e.add_doc(did)

    real_dispatch = rset._dispatch_final
    fail_next = {"mode": None}

    def flaky(trip_list, route, interpret):
        if fail_next["mode"] == "dispatch":
            fail_next["mode"] = None
            raise RuntimeError("injected dispatch failure")
        return real_dispatch(trip_list, route, interpret)

    rset._dispatch_final = flaky
    n_injected = 0
    for k, deltas in enumerate(rounds):
        roll = rng.random()
        if roll < 0.35:
            fail_next["mode"] = "dispatch"
            n_injected += 1
        with e.batch():
            for did, chs in deltas.items():
                e.apply_changes(did, chs)
        # the engine object survives (no rebuild on this path), so the
        # monkeypatch stays active; re-assert it is still in place
        assert e._resident is rset
        if roll >= 0.8:
            # mid-stream readback failure: poison the cached handle
            class Boom:
                def __array__(self, *a, **kw):
                    raise RuntimeError("injected readback failure")
            rset._hash_handle = Boom()
            with pytest.raises(DeviceDispatchError):
                rset.hashes()
            n_injected += 1
    rset._dispatch_final = real_dispatch
    assert n_injected >= 2, "soak injected too few failures to mean much"

    # every doc converges to the oracle hash and to a clean node
    clean = EngineDocSet(backend="rows")
    for did, chs in finals.items():
        clean.add_doc(did)
        clean.apply_changes(did, chs)
    h, hc = e.hashes(), clean.hashes()
    for did, chs in finals.items():
        want = oracle_hash(chs)
        assert np.uint32(h[did]) == want, did
        assert np.uint32(hc[did]) == want, did
        # exactly-once admission: log length == total changes
        assert (len(rset.change_log[rset.doc_index[did]])
                == len(chs)), did
        assert e.materialize(did) == clean.materialize(did), did
