"""Adaptive backend routing (engine/dispatch.py): workloads below the
host<->device link's fixed-cost floor run on the host path; DocSet-scale
batches go to the device. The reference has no such choice to make (one
JS path); for this framework the router IS the product path."""

import automerge_tpu as am
from automerge_tpu.engine.dispatch import (Plan, apply_batch_adaptive,
                                           apply_host, plan_batch)
from automerge_tpu.frontend.materialize import apply_changes_to_doc


def _trace_small():
    d = am.change(am.init("A"), lambda x: am.assign(x, {"n": 1, "xs": [1, 2]}))
    d = am.change(d, lambda x: x["xs"].insert_at(1, 9))
    return d._doc.opset.get_missing_changes({})


def _trace_bulk(n=200):
    d = am.change(am.init("A"), lambda x: x.__setitem__("xs", []))
    for i in range(n):
        d = am.change(d, lambda x, i=i: x["xs"].insert_at(len(x["xs"]), i))
    return d._doc.opset.get_missing_changes({})


def test_plan_small_single_doc_routes_host():
    p = plan_batch(n_docs=1, n_ops=200, wire_bytes=120 * 128 * 4)
    assert p.backend == "host"
    assert p.est_host_s < p.est_device_s


def test_plan_docset_batch_routes_device():
    p = plan_batch(n_docs=10_000, n_ops=80_000, wire_bytes=5_000_000,
                   passes=10)
    assert p.backend == "device"


def test_apply_host_interpretive_parity():
    changes = _trace_small()
    got = apply_host(changes)
    doc = am.init("oracle")
    want = apply_changes_to_doc(doc, doc._doc.opset, changes,
                                incremental=False)
    assert am.equals(got, want)


def test_apply_host_bulk_parity():
    changes = _trace_bulk()
    got = apply_host(changes)  # bulk build engages at this size
    doc = am.init("oracle")
    want = apply_changes_to_doc(doc, doc._doc.opset, changes,
                                incremental=False)
    assert am.equals(got, want)
    assert am.save(got) == am.save(want)


def test_adaptive_small_batch_returns_host_docs():
    doc_changes = [_trace_small(), _trace_bulk(80)]
    plan, result = apply_batch_adaptive(doc_changes)
    assert isinstance(plan, Plan) and plan.backend == "host"
    assert len(result) == 2
    for chs, got in zip(doc_changes, result):
        doc = am.init("oracle")
        want = apply_changes_to_doc(doc, doc._doc.opset, chs,
                                    incremental=False)
        assert am.equals(got, want)


def _trace_concurrent(n_per_actor=100):
    """Merged multi-actor doc: get_missing_changes emits per-actor runs
    whose deps cross runs — NOT causal application order."""
    a = am.change(am.init("A"), lambda x: x.__setitem__("xs", []))
    b = am.merge(am.init("B"), a)
    c = am.merge(am.init("C"), a)
    for i in range(n_per_actor):
        a = am.change(a, lambda x, i=i: x.__setitem__(f"a{i % 9}", i))
        b = am.change(b, lambda x, i=i: x["xs"].insert_at(0, i))
        c = am.change(c, lambda x, i=i: x.__setitem__(f"c{i % 9}", -i))
    m = am.merge(am.merge(a, b), c)
    return m._doc.opset.get_missing_changes({})


def test_causal_order_passthrough_and_reorder():
    from automerge_tpu.engine.dispatch import _causal_order

    linear = _trace_bulk(20)
    assert _causal_order(linear) is linear  # already causal: no copy

    # force a non-causal permutation: per-actor runs with the dependent
    # actors' runs FIRST (their deps point at changes that come later)
    conc = _trace_concurrent(10)
    shuffled = sorted(conc, key=lambda c: (c.actor != "C", c.actor != "B",
                                           c.seq))
    assert _causal_order(shuffled) is not shuffled  # really non-causal
    ordered = _causal_order(shuffled)
    assert ordered is not None
    assert sorted((c.actor, c.seq) for c in ordered) \
        == sorted((c.actor, c.seq) for c in shuffled)
    clock = {}
    for c in ordered:
        assert c.seq == clock.get(c.actor, 0) + 1
        assert all(clock.get(a, 0) >= s for a, s in c.deps.items())
        clock[c.actor] = c.seq

    # an incomplete log has no causal order -> interpretive semantics
    assert _causal_order(shuffled[1:]) is None


def test_apply_host_bulk_engages_on_concurrent_log(monkeypatch):
    """The r3 bench's config-3 routing tax: a merged multi-actor log used
    to pay a failed bulk attempt (causal-order bail) and fall back. After
    the stable reorder, bulk must ENGAGE and match the interpretive result
    exactly. (The threshold is lowered for the test: the r5 no-diff
    interpretive mode pushed the real crossover to tens of thousands of
    changes; this pins the engagement MECHANISM, not the constant.)"""
    from automerge_tpu.engine import dispatch as _dispatch
    monkeypatch.setattr(_dispatch, "HOST_BULK_MIN_CHANGES", 256)
    changes = _trace_concurrent()
    assert len(changes) >= 256
    am.metrics.reset()
    got = apply_host(changes)
    doc = am.init("oracle")
    want = apply_changes_to_doc(doc, doc._doc.opset, changes,
                                incremental=False)
    assert am.equals(got, want)
    snap = am.metrics.snapshot()
    assert snap.get("core_bulk_fallbacks", 0) == 0
    # positive signal: the bulk path really built (not interpretive)
    assert snap.get("engine_bulk_built", 0) == 1, snap


def test_causal_order_property_random_shuffles():
    """Property: for ANY permutation of a complete change log, _causal_order
    returns a valid causal order containing exactly the same changes; for
    any log with a change removed, it returns None."""
    import random as _random

    from automerge_tpu.engine.dispatch import _causal_order

    rng = _random.Random(123)
    conc = _trace_concurrent(8)
    for trial in range(25):
        shuffled = list(conc)
        rng.shuffle(shuffled)
        ordered = _causal_order(shuffled)
        assert ordered is not None
        assert sorted((c.actor, c.seq) for c in ordered) \
            == sorted((c.actor, c.seq) for c in conc)
        clock = {}
        for c in ordered:
            assert c.seq == clock.get(c.actor, 0) + 1
            assert all(clock.get(a, 0) >= s for a, s in c.deps.items())
            clock[c.actor] = c.seq
        # drop one random change: no causal order may exist for the rest
        # of that actor's chain (and usually for cross-actor dependents)
        k = rng.randrange(len(shuffled))
        broken = shuffled[:k] + shuffled[k + 1:]
        got = _causal_order(broken)
        if got is not None:
            # legal only if nothing depended on the dropped change and it
            # was the tail of its actor chain
            dropped = shuffled[k]
            assert all(c.actor != dropped.actor or c.seq < dropped.seq
                       for c in broken)
            assert all(c.deps.get(dropped.actor, 0) < dropped.seq
                       for c in broken)
