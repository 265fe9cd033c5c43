"""The tenant attribution plane (sync/tenantledger.py): the doc-id
namespace derivation rule, the house ledger contract (bounded tenant
table with disclosed overflow, pure-state export, env-var disable as one
cached check), proportional round attribution, the tenantplane
attribution check, and the `tenant_storm` chaos fault.
"""

import time

import pytest

from automerge_tpu.perf import tenantplane
from automerge_tpu.sync import tenantledger
from automerge_tpu.utils import chaos, flightrec, metrics

TENANT_VARS = ("AMTPU_TENANTLEDGER", "AMTPU_TENANT_PREFIX")
STORM_VARS = ("AMTPU_CHAOS_TENANT_STORM", "AMTPU_CHAOS_TENANT_STORM_X",
              "AMTPU_CHAOS_NODE")


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    """Every test starts and ends with a pristine tenant/chaos config
    and an empty ledger."""
    for var in TENANT_VARS + STORM_VARS:
        monkeypatch.delenv(var, raising=False)
    tenantledger._reload_for_tests()
    chaos.reload()
    metrics.reset()          # runs the registered reset hook too
    flightrec.reset()
    yield
    for var in TENANT_VARS + STORM_VARS:
        monkeypatch.delenv(var, raising=False)
    tenantledger._reload_for_tests()
    chaos.reload()
    metrics.reset()
    flightrec.reset()


# ---------------------------------------------------------------------------
# derivation rule


def test_tenant_of_prefix_rule():
    assert tenantledger.tenant_of("tenant/acme/orders-1") == "acme"
    assert tenantledger.tenant_of("tenant/acme") == "acme"
    assert tenantledger.tenant_of("tenant/a/b/c") == "a"
    assert tenantledger.tenant_of("orders-1") == "_default"
    # a bare prefix with no id falls back rather than minting ""
    assert tenantledger.tenant_of("tenant/") == "_default"
    assert tenantledger.tenant_of("") == "_default"


def test_tenant_of_prefix_override(monkeypatch):
    monkeypatch.setenv("AMTPU_TENANT_PREFIX", "org:")
    tenantledger._reload_for_tests()
    assert tenantledger.tenant_of("org:acme/doc") == "acme"
    assert tenantledger.tenant_of("tenant/acme/doc") == "_default"


# ---------------------------------------------------------------------------
# disable contract


def test_disabled_hooks_record_nothing(monkeypatch):
    monkeypatch.setenv("AMTPU_TENANTLEDGER", "0")
    tenantledger._reload_for_tests()
    tenantledger.note_ingress("tenant/a/d", 5)
    tenantledger.note_wire("tenant/a/d", sent=3, bytes_sent=100)
    tenantledger.note_lag("tenant/a/d", 0.5)
    tenantledger.note_shed("tenant/a/d", delayed=False)
    tenantledger.note_round({"a": 1}, {"dispatches": 4})
    assert tenantledger.note_ingress_round({"tenant/a/d": 2}) is None
    assert tenantledger.ledger().section() is None
    assert tenantledger.snapshot_section() is None
    snap = metrics.snapshot()
    assert "tenantledger" not in snap
    assert not any(k.startswith("sync_tenant_") for k in snap)


# ---------------------------------------------------------------------------
# accounting + export


def _feed_basic():
    tenantledger.note_ingress("tenant/a/d1", 6)
    tenantledger.note_ingress("tenant/b/d1", 2)
    tenantledger.note_ingress("plain-doc", 2)
    tenantledger.note_wire("tenant/a/d1", sent=4, bytes_sent=400,
                           useful=3, dup=1, bytes_recv=300, drops=1)
    tenantledger.note_lag("tenant/a/d1", 0.25)
    tenantledger.note_shed("tenant/b/d1", delayed=True, delay_s=0.01)
    tenantledger.note_shed("tenant/b/d1", delayed=False)


def test_section_accounts_and_shares():
    _feed_basic()
    sec = tenantledger.ledger().section()
    assert sec["admitted_total"] == 10
    assert sec["tracked"] == 3 and sec["truncated"] == 0
    a = sec["tenants"]["a"]
    assert a["admitted"] == 6
    assert a["ingress_share_pct"] == 60.0
    assert a["sent"] == 4 and a["bytes_sent"] == 400
    assert a["recv_useful"] == 3 and a["recv_duplicate"] == 1
    assert a["drops"] == 1
    assert a["lag"]["p99_s"] == 0.25 and a["lag"]["max_s"] == 0.25
    b = sec["tenants"]["b"]
    assert b["shed_delayed"] == 1 and b["shed_dropped"] == 1
    assert sec["tenants"]["_default"]["admitted"] == 2
    # hottest-ingress ranks first
    assert list(sec["tenants"])[0] == "a"


# the flush's round call: one write a tenant, what one call a doc leaves

ROUND_NOW = 1_000.0


def _ingress_as_the_seed_did(doc_id, n_changes):
    """`note_ingress` as it stood before the round call: one
    `_tenant_locked` (slot or overflow, the gauge cadence) and the stamps,
    a document."""
    led = tenantledger.ledger()
    if not tenantledger.enabled() or n_changes <= 0:
        return
    tid = tenantledger.tenant_of(doc_id)
    with led._lock:
        t = led._tenant_locked(tid)
        t.admitted += int(n_changes)
        t.admit_events += 1
        t.last_admit_at = ROUND_NOW
        led._admitted_total += int(n_changes)


def _seed_one_by_one(counts):
    for d, n in counts.items():
        _ingress_as_the_seed_did(d, n)


def _one_by_one(counts):
    for d, n in counts.items():
        tenantledger.note_ingress(d, n)


# every case counts a multiple of GAUGE_REFRESH mutations, so the last
# refresh of a one-by-one feed sees every tenant the round makes
INGRESS_ROUNDS = {
    "default-tenant-only": ({}, [{f"doc-{i}": 1 + i % 2 for i in range(64)}]),
    "prefixed-and-default-tenants": ({}, [
        {(f"tenant/t{i % 5}/d{i}" if i % 3 else f"plain{i}"): 1 + i % 4
         for i in range(64)},
        {(f"tenant/t{i % 7}/d{i}" if i % 2 else f"plain{i}"): 2
         for i in range(32, 64)}]),
    "a-zero-count-is-passed-over": ({}, [
        {f"tenant/t{i % 3}/d{i}": (0 if i % 4 == 0 else -1 if i == 5 else 2)
         for i in range(87)}]),          # 22 zeros, one negative: 64 count
    "more-tenants-than-the-table-holds": ({}, [
        {f"tenant/t{i % 80}/d{i}": 1 for i in range(160)}]),
    "the-plane-disabled": ({"AMTPU_TENANTLEDGER": "0"}, [
        {f"tenant/t{i % 5}/d{i}": 1 for i in range(64)}]),
}


def _tenant_state():
    led = tenantledger.ledger()
    sec = led.section()
    if sec is not None:
        sec.pop("self_s")
    snap = metrics.snapshot()
    return {"section": sec, "mutations": led._mutations,
            "overflowed": led._overflowed,
            "order": list(led._tenants),
            "overflow_counter": snap.get("sync_tenant_overflow"),
            "tracked_gauge": snap.get("obs_tenant_tracked")}


@pytest.mark.parametrize("case", sorted(INGRESS_ROUNDS))
def test_an_ingress_round_leaves_what_one_call_a_document_leaves(
        case, monkeypatch):
    env, rounds = INGRESS_ROUNDS[case]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(time, "time", lambda: ROUND_NOW)
    led = tenantledger.ledger()
    real = led._refresh_gauges_locked
    refreshes = []
    monkeypatch.setattr(led, "_refresh_gauges_locked",
                        lambda: (refreshes.append(1), real()))
    left = {}
    for feed in ("seed", "one-by-one", "round"):
        tenantledger._reload_for_tests()
        metrics.reset()
        for counts in rounds:
            before = len(refreshes)
            {"seed": _seed_one_by_one, "one-by-one": _one_by_one,
             "round": tenantledger.note_ingress_round}[feed](counts)
            if feed == "round":
                assert len(refreshes) - before <= 1
        left[feed] = _tenant_state()
    assert left["round"] == left["seed"]
    assert left["one-by-one"] == left["seed"]
    got = left["round"]
    if env:
        assert got["section"] is None and got["mutations"] == 0
        return
    assert got["mutations"] % tenantledger.GAUGE_REFRESH == 0
    counted = [n for counts in rounds for n in counts.values() if n > 0]
    assert got["section"]["admitted_total"] == sum(counted)
    assert sum(t["admit_events"]
               for t in got["section"]["tenants"].values()) <= len(counted)
    assert got["mutations"] == len(counted)
    if case == "more-tenants-than-the-table-holds":
        # 16 tenants of 80 find the table full, twice each but for t48..t63
        assert got["overflowed"] == got["overflow_counter"] == 16 + 16


def test_idle_snapshots_byte_equal():
    _feed_basic()
    tenantledger.note_round({"a": 3, "b": 1}, {"dispatches": 8,
                                               "wall_s": 0.02})
    s1 = tenantledger.snapshot_section()
    s2 = tenantledger.snapshot_section()
    assert s1 == s2                      # pure export: no clock reads


def test_round_attribution_is_proportional():
    folded = {"dispatches": 6, "ambient": 2, "padded": 400,
              "logical": 100, "wall_s": 0.08}
    tenantledger.note_round({"a": 3, "b": 1}, folded)
    sec = tenantledger.ledger().section()
    a, b = sec["tenants"]["a"], sec["tenants"]["b"]
    assert a["dispatch_share"] == 6.0 and b["dispatch_share"] == 2.0
    assert a["padded_share"] == 300.0 and b["padded_share"] == 100.0
    assert a["logical_share"] == 75.0 and b["logical_share"] == 25.0
    assert a["wall_share_s"] == pytest.approx(0.06)
    assert a["dirty_docs"] == 3 and a["rounds"] == 1
    assert sec["rounds_total"] == 1


def test_overflow_folds_with_disclosure():
    for k in range(tenantledger.MAX_TENANTS + 5):
        tenantledger.note_ingress(f"tenant/t{k}/d", 1)
    sec = tenantledger.ledger().section()
    assert sec["tracked"] == tenantledger.MAX_TENANTS + 1  # + _overflow
    assert sec["overflow_tenants"] == 5
    assert sec["admitted_total"] == tenantledger.MAX_TENANTS + 5
    # identity folds but the counts survive
    snap = metrics.snapshot()
    assert snap.get("sync_tenant_overflow") == 5
    assert sum(t.admitted for t in
               tenantledger.ledger()._tenants.values()) == \
        tenantledger.MAX_TENANTS + 5


def test_the_round_fold_gives_the_dispatch_split_its_documents():
    """One fold by tenant a round: note_ingress_round returns the
    admitted documents by tenant, the dispatch ledger's split; a
    document that admitted nothing dirtied no lane and is not in it."""
    got = tenantledger.note_ingress_round(
        {"tenant/a/1": 2, "tenant/a/2": 1, "tenant/b/1": 1, "plain": 3,
         "tenant/c/1": 0})
    assert got == {"a": 2, "b": 1, "_default": 1}
    assert tenantledger.note_ingress_round({"tenant/c/1": 0}) is None


def test_snapshot_section_rides_metrics_snapshot_and_reset():
    _feed_basic()
    snap = metrics.snapshot()
    nodes = (snap.get("tenantledger") or {}).get("nodes")
    assert nodes and any("a" in sec["tenants"]
                         for sec in nodes.values())
    metrics.reset()          # registered reset hook clears the ledger
    assert tenantledger.ledger().section() is None


def test_attribution_check_sums_to_totals():
    _feed_basic()
    tenantledger.note_round({"a": 1}, {"dispatches": 2})
    sec = tenantledger.ledger().section()
    chk = tenantplane.attribution_check(sec)
    assert chk["admitted_sum"] == chk["admitted_total"] == 10
    assert chk["err_pct"] == 0.0
    assert chk["complete"] is True


def test_self_time_accumulates():
    _feed_basic()
    assert tenantledger.ledger().self_seconds() > 0.0


# ---------------------------------------------------------------------------
# tenant_storm chaos fault


def test_tenant_storm_inert_when_unset():
    assert chaos.tenant_storm("n0", "tenant/a/d") == 0
    assert metrics.snapshot().get(
        "obs_chaos_injected{fault=tenant_storm}") is None


def test_tenant_storm_fires_for_target_tenant_only(monkeypatch):
    monkeypatch.setenv("AMTPU_CHAOS_TENANT_STORM", "hot")
    monkeypatch.setenv("AMTPU_CHAOS_TENANT_STORM_X", "4")
    chaos.reload()
    assert chaos.tenant_storm("n0", "tenant/hot/d") == 3   # x - 1 extras
    assert chaos.tenant_storm("n0", "tenant/quiet/d") == 0
    assert chaos.tenant_storm("n0", "plain") == 0
    snap = metrics.snapshot()
    assert snap.get("obs_chaos_injected{fault=tenant_storm}") == 1


def test_tenant_storm_respects_node_targeting(monkeypatch):
    monkeypatch.setenv("AMTPU_CHAOS_TENANT_STORM", "hot")
    monkeypatch.setenv("AMTPU_CHAOS_NODE", "victim")
    chaos.reload()
    assert chaos.tenant_storm("bystander", "tenant/hot/d") == 0
    assert chaos.tenant_storm("victim", "tenant/hot/d") > 0


def test_tenant_storm_reload_clears(monkeypatch):
    monkeypatch.setenv("AMTPU_CHAOS_TENANT_STORM", "hot")
    chaos.reload()
    assert chaos.tenant_storm("n0", "tenant/hot/d") > 0
    monkeypatch.delenv("AMTPU_CHAOS_TENANT_STORM")
    chaos.reload()
    assert chaos.tenant_storm("n0", "tenant/hot/d") == 0
