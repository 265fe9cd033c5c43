"""Fleet megabatching (r20): fused multi-doc round dispatch.

The invariant every test here pins: a megabatched round's converged
hashes are BYTE-IDENTICAL to the per-doc path's, because each bucket's
gather is a pure row-index subset of the full docs-minor layout
(engine/pack.py mega_row_map). Doc identity is actor-random at init, so
parity tests generate each change set ONCE and replay it into every
service under comparison — rebuilding a "same" doc yields different
hashes by design.

Routing is cost-model driven and the baked-in link constants price
dispatches at TPU PCIe cost, so service-level tests recalibrate to
CPU-scale constants (the `cpu_link` fixture of conftest.py) and grow the resident caps with one large
doc so a small-doc storm's fused subset gather beats the classic
full-layout gather — the regime ROADMAP #2 targets, reproduced small.
"""

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.engine import dispatch, dispatchledger, pack
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import metrics


def eager(svc):
    svc._lazy_resolved = True
    svc._resident.lazy_dispatch = False
    return svc


def big_doc_changes(n_ops=96):
    doc = am.change(am.init("big"), lambda d: am.assign(
        d, {"items": list(range(n_ops)), "meta": {"kind": "big"}}))
    return doc._doc.opset.get_missing_changes({})


def small_doc_changes(i):
    doc = am.change(am.init(f"w{i:03d}"), lambda d: am.assign(
        d, {"x": i, "tags": ["a", "b"]}))
    return doc._doc.opset.get_missing_changes({})


def run_fleet(changes, mega, monkeypatch=None):
    """Replay (doc_id, changes) pairs: the first pair alone (grows
    caps), the rest as ONE coalesced storm round. Returns hashes."""
    if not mega:
        assert monkeypatch is not None
        monkeypatch.setenv("AMTPU_MEGABATCH", "0")
    dispatch._reload_for_tests()
    svc = eager(EngineDocSet(backend="rows"))
    try:
        did0, chs0 = changes[0]
        svc.apply_changes(did0, chs0)
        svc.hashes()
        with svc.batch():
            for did, chs in changes[1:]:
                svc.apply_changes(did, chs)
        return {d: np.uint32(h) for d, h in svc.hashes().items()}
    finally:
        svc.close()
        if not mega:
            monkeypatch.delenv("AMTPU_MEGABATCH", raising=False)
        dispatch._reload_for_tests()


def mega_totals():
    sec = dispatchledger.ledger().section() or {}
    return {k: int(sec.get(f"mega_{k}_total") or 0)
            for k in ("rounds", "dispatches", "docs")}


# ---------------------------------------------------------------------------
# pack: quantize / row map / bucket planning


def test_mega_quantize_power_of_two_ladder():
    assert pack.mega_quantize(1, 256) == pack.MEGA_MIN_DIM
    assert pack.mega_quantize(8, 256) == 8
    assert pack.mega_quantize(9, 256) == 16
    assert pack.mega_quantize(100, 256) == 128
    # clamped at the cap even off-ladder
    assert pack.mega_quantize(100, 96) == 96
    assert pack.mega_quantize(0, 96) == pack.MEGA_MIN_DIM


def test_mega_row_map_is_an_exact_subset():
    i, a, le = 64, 2, 8 * 16
    i_b, le_b = 16, 2 * 16
    rmap = pack.mega_row_map(i, a, le, i_b, le_b)
    full = pack.rows_count(i, a, le)
    assert len(rmap) == pack.rows_count(i_b, a, le_b)
    assert len(set(rmap.tolist())) == len(rmap)      # no row twice
    assert rmap.min() >= 0 and rmap.max() < full     # inside the layout


def test_mega_row_map_full_dims_is_identity():
    i, a, le = 32, 3, 4 * 8
    rmap = pack.mega_row_map(i, a, le, i, le)
    assert np.array_equal(rmap, np.arange(pack.rows_count(i, a, le)))


def test_plan_megabuckets_caps_bucket_count():
    # pathological spread: every doc a different size
    i_used = np.asarray([1, 3, 7, 15, 31, 63, 127, 200, 9, 80],
                        np.int64)
    l_used = np.asarray([0, 1, 2, 4, 8, 16, 3, 30, 0, 12], np.int64)
    caps = (256, 2, 32 * 16)
    buckets = pack.plan_megabuckets(i_used, l_used, caps, 16)
    assert 1 <= len(buckets) <= pack.MEGA_MAX_BUCKETS
    # every doc position lands in exactly one bucket...
    seen = sorted(p for b in buckets for p in b["docs"].tolist())
    assert seen == list(range(len(i_used)))
    # ...whose dims cover its used sizes (no truncated reconcile)
    for b in buckets:
        i_b, le_b = b["dims"]
        for p in b["docs"].tolist():
            assert i_b >= i_used[p]
            assert le_b >= l_used[p] * 16 or le_b == caps[2]


# ---------------------------------------------------------------------------
# routing


def test_one_doc_round_stays_per_doc(cpu_link):
    svc = eager(EngineDocSet(backend="rows"))
    try:
        svc.apply_changes("a", small_doc_changes(0))
        svc.apply_changes("b", small_doc_changes(1))
        svc.hashes()
        rset = svc._resident
        plan = dispatch.plan_round(rset, [0])
        assert plan.route == "per_doc"          # below the doc floor
        assert dispatch.apply_round_adaptive(rset, plan) is None
    finally:
        svc.close()


def test_disabled_env_short_circuits_planning(cpu_link, monkeypatch):
    monkeypatch.setenv("AMTPU_MEGABATCH", "0")
    dispatch._reload_for_tests()
    try:
        svc = eager(EngineDocSet(backend="rows"))
        try:
            for i in range(6):
                svc.apply_changes(f"d{i}", small_doc_changes(i))
            svc.hashes()
            plan = dispatch.plan_round(svc._resident, list(range(6)))
            assert plan.route == "per_doc"
            assert plan.buckets == []           # never even planned
        finally:
            svc.close()
    finally:
        monkeypatch.delenv("AMTPU_MEGABATCH", raising=False)
        dispatch._reload_for_tests()


def test_planner_never_picks_a_costlier_fused_plan(cpu_link):
    """Pathological spread: whatever the route, the executed side of
    the cost comparison is the cheaper one — fused amplification can
    never exceed the per-doc baseline by construction."""
    svc = eager(EngineDocSet(backend="rows"))
    try:
        svc.apply_changes("big", big_doc_changes(120))
        for i in range(8):
            # one shared actor id across docs: the actor axis is pooled
            # fleet-wide and scales every row band
            doc = am.change(am.init("W"), lambda d, i=i: am.assign(
                d, {"v": i, "pad": list(range(1 + 4 * i))}))
            svc.apply_changes(f"d{i}",
                              doc._doc.opset.get_missing_changes({}))
        svc.hashes()
        plan = dispatch.plan_round(svc._resident, list(range(1, 9)))
        assert plan.buckets and \
            len(plan.buckets) <= pack.MEGA_MAX_BUCKETS
        if plan.route == "megabatch":
            assert plan.est_mega_s <= plan.est_alt_s
        else:
            assert plan.est_mega_s > plan.est_alt_s
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# byte parity vs the per-doc path (the tentpole invariant)


def test_same_shape_storm_one_bucket_one_dispatch(cpu_link, monkeypatch):
    changes = [("doc-big", big_doc_changes())]
    changes += [(f"doc{i:03d}", small_doc_changes(i)) for i in range(12)]
    base = mega_totals()
    fused = run_fleet(changes, mega=True)
    after = mega_totals()
    classic = run_fleet(changes, mega=False, monkeypatch=monkeypatch)
    assert fused == classic                     # byte-equal, all docs
    assert after["rounds"] - base["rounds"] == 1
    assert after["dispatches"] - base["dispatches"] == 1  # one bucket
    assert after["docs"] - base["docs"] == 12


def test_mixed_shape_storm_byte_equal(cpu_link, monkeypatch):
    # two shape clusters (tiny maps vs mid-size lists): few buckets,
    # each far below the full layout — the fused plan's home turf
    changes = [("doc-big", big_doc_changes(96))]
    for i in range(10):
        n_xs = 2 if i % 2 == 0 else 18
        doc = am.change(am.init("W"), lambda d, i=i, n=n_xs: am.assign(
            d, {"n": i, "xs": list(range(n))}))
        changes.append((f"doc{i:02d}",
                        doc._doc.opset.get_missing_changes({})))
    base = mega_totals()
    fused = run_fleet(changes, mega=True)
    after = mega_totals()
    classic = run_fleet(changes, mega=False, monkeypatch=monkeypatch)
    assert fused == classic
    assert after["rounds"] > base["rounds"]
    assert after["dispatches"] - base["dispatches"] <= \
        pack.MEGA_MAX_BUCKETS


def test_mixed_map_list_move_round_byte_equal(cpu_link, monkeypatch):
    """Raw map/list/move ops through the fused round — the op families
    bench config 16/20 mix, each doc's change set shared verbatim."""
    def doc_changes(i):
        ops = [Op("makeMap", f"f{i}a"), Op("makeMap", f"f{i}b"),
               Op("link", ROOT_ID, key="ka", value=f"f{i}a"),
               Op("link", ROOT_ID, key="kb", value=f"f{i}b"),
               Op("makeList", f"L{i}"),
               Op("link", ROOT_ID, key="L", value=f"L{i}")]
        prev = "_head"
        for e in range(1, 3 + i % 4):
            ops.append(Op("ins", f"L{i}", key=prev, elem=e))
            ops.append(Op("set", f"L{i}", key=f"A:{e}", value=e * 10))
            prev = f"A:{e}"
        chs = [Change("A", 1, {}, ops),
               Change("A", 2, {},
                      [Op("move", f"f{i}b", key="moved",
                          value=f"f{i}a")])]
        return chs

    changes = [("doc-big", big_doc_changes())]
    changes += [(f"doc{i}", doc_changes(i)) for i in range(9)]
    fused = run_fleet(changes, mega=True)
    classic = run_fleet(changes, mega=False, monkeypatch=monkeypatch)
    assert fused == classic


def test_both_orders_storm_converges_through_megabatch(cpu_link):
    """Two concurrent writers per doc, applied in opposite orders on
    two megabatched services: same converged hash per doc — CRDT
    convergence survives lane sharing."""
    big_chs = big_doc_changes()         # ONE shared change set: doc
    per_doc = []                        # init is actor-random
    for i in range(8):
        a = am.change(am.init(f"A{i}"),
                      lambda d, i=i: am.assign(d, {"x": i, "l": [i]}))
        b = am.merge(am.init(f"B{i}"), a)
        a2 = am.change(a, lambda d: d.__setitem__("x", 99))
        b2 = am.change(b, lambda d: d["l"].append(7))
        clk = {c.actor: c.seq
               for c in a._doc.opset.get_missing_changes({})}
        per_doc.append((a._doc.opset.get_missing_changes({}),
                        a2._doc.opset.get_missing_changes(clk),
                        b2._doc.opset.get_missing_changes(clk)))

    def storm(order):
        dispatch._reload_for_tests()
        svc = eager(EngineDocSet(backend="rows"))
        try:
            svc.apply_changes("doc-big", big_chs)
            svc.hashes()
            with svc.batch():
                for i, (base, da, db) in enumerate(per_doc):
                    svc.apply_changes(f"d{i}", base)
            first, second = (1, 2) if order == "ab" else (2, 1)
            with svc.batch():
                for i, chs in enumerate(per_doc):
                    svc.apply_changes(f"d{i}", chs[first])
            with svc.batch():
                for i, chs in enumerate(per_doc):
                    svc.apply_changes(f"d{i}", chs[second])
            return {d: np.uint32(h) for d, h in svc.hashes().items()}
        finally:
            svc.close()

    assert storm("ab") == storm("ba")


def test_fused_dispatch_failure_recovers_byte_equal(cpu_link, monkeypatch):
    """A device failure inside the fused bucket dispatch surfaces as
    DeviceDispatchError(admission_complete=True) — host truth already
    holds the round, so the sync service swallows it without replay and
    the next hash read reconciles the still-dirty lanes byte-equal to
    the classic path (the r20 counterpart of the per-doc failure soak in
    tests/test_soak_failure_injection.py)."""
    changes = [("doc-big", big_doc_changes())]
    changes += [(f"doc{i:03d}", small_doc_changes(i)) for i in range(8)]
    classic = run_fleet(changes, mega=False, monkeypatch=monkeypatch)
    dispatch._reload_for_tests()
    svc = eager(EngineDocSet(backend="rows"))
    try:
        did0, chs0 = changes[0]
        svc.apply_changes(did0, chs0)
        svc.hashes()
        rset = svc._resident
        real = rset._to_dev
        armed = {"now": True}

        def flaky(x):
            if armed["now"]:
                armed["now"] = False
                raise RuntimeError("injected fused dispatch failure")
            return real(x)

        monkeypatch.setattr(rset, "_to_dev", flaky)
        failed_before = metrics.snapshot().get("rows_dispatch_failed", 0)
        with svc.batch():
            for did, chs in changes[1:]:
                svc.apply_changes(did, chs)
        failed_after = metrics.snapshot().get("rows_dispatch_failed", 0)
        assert failed_after - failed_before >= 1    # injection fired
        assert not armed["now"]
        got = {d: np.uint32(h) for d, h in svc.hashes().items()}
        assert got == classic
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# occupancy accounting rides the fused round


def test_fused_round_summary_and_ledger_account(cpu_link):
    changes = [("doc-big", big_doc_changes())]
    changes += [(f"doc{i:03d}", small_doc_changes(i)) for i in range(12)]
    base = mega_totals()
    run_fleet(changes, mega=True)
    sec = dispatchledger.ledger().section() or {}
    after = mega_totals()
    assert after["docs"] - base["docs"] == 12
    assert int(sec.get("mega_docs_cap_total") or 0) > 0
