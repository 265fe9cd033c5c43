"""ShardedEngineDocSet: one sync-node surface over K engine shards —
Connection-protocol convergence against a plain node, burst coalescing to
at most one dispatch per shard, stable routing, and oracle hash parity."""

import numpy as np

import automerge_tpu as am
from automerge_tpu.sync.connection import Connection
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet

from tests.test_rows_service import oracle_hash, two_replica_trace, drain


def _mk(i):
    d = am.change(am.init("W"), lambda x, i=i: am.assign(
        x, {"n": i, "xs": [i]}))
    return d._doc.opset.get_missing_changes({})


def test_routing_is_stable_and_total():
    e = ShardedEngineDocSet(n_shards=3)
    ids = [f"d{i}" for i in range(40)]
    for did in ids:
        e.add_doc(did)
    assert sorted(e.doc_ids) == sorted(ids)
    for did in ids:
        assert e.shard_of(did) is e.shard_of(did)
    per = [len(s.doc_ids) for s in e.shards]
    assert sum(per) == len(ids) and all(p > 0 for p in per), per


def test_burst_coalesces_to_one_dispatch_per_shard():
    am.metrics.reset()
    e = ShardedEngineDocSet(n_shards=2)
    hashes_want = {}
    with e.batch():
        for i in range(12):
            chs = _mk(i)
            e.apply_changes(f"d{i}", chs)
            hashes_want[f"d{i}"] = oracle_hash(chs)
    snap = am.metrics.snapshot()
    rounds = sum(v for k, v in snap.items()
                 if k.startswith("sync_rounds_flushed"))
    # at least one round dispatched AT batch exit (not deferred to the
    # hashes() read below), at most one per shard
    assert 1 <= rounds <= e.n_shards, snap
    h = e.hashes()
    for did, want in hashes_want.items():
        assert np.uint32(h[did]) == want, did
        assert e.materialize(did)["data"]["n"] == int(did[1:])


def test_sharded_node_converges_with_plain_node_over_connection():
    chs_a, chs_b, chs_all = two_replica_trace()
    qa, qb = [], []
    sharded = ShardedEngineDocSet(n_shards=3)
    from automerge_tpu.sync.service import EngineDocSet
    plain = EngineDocSet(backend="rows")
    ca = Connection(sharded, qa.append, wire="columnar")
    cb = Connection(plain, qb.append, wire="columnar")
    sharded.add_doc("d")
    plain.add_doc("d")
    ca.open()
    cb.open()
    sharded.apply_changes("d", chs_a)
    plain.apply_changes("d", chs_b)
    drain(qa, ca, qb, cb)
    want = oracle_hash(chs_all)
    assert np.uint32(sharded.hashes()["d"]) == want
    assert np.uint32(plain.hashes()["d"]) == want
    assert sharded.materialize("d") == plain.materialize("d")


def test_poisoned_shard_is_isolated():
    """A poisoned shard (unrecoverable mid-admission failure) must fail
    loudly on ITS docs while the other shards keep serving theirs; the
    fleet-wide hashes() read surfaces the poison rather than silently
    dropping the shard."""
    import pytest

    e = ShardedEngineDocSet(n_shards=2)
    ids = [f"d{i}" for i in range(8)]
    chs = {did: _mk(i) for i, did in enumerate(ids)}
    for did in ids:
        e.apply_changes(did, chs[did])
    sick = e.shards[0]
    healthy = e.shards[1]
    sick_doc = next(d for d in ids if e.shard_of(d) is sick)
    ok_doc = next(d for d in ids if e.shard_of(d) is healthy)

    sick._resident._poison(RuntimeError("injected"))
    # healthy shard unaffected
    assert e.materialize(ok_doc)["data"]["n"] == int(ok_doc[1:])
    assert np.uint32(healthy.hashes()[ok_doc]) == oracle_hash(chs[ok_doc])
    # sick shard's docs fail loudly, as does the fleet-wide read
    with pytest.raises(RuntimeError, match="no longer reflects"):
        e.shard_of(sick_doc).hashes()
    with pytest.raises(RuntimeError, match="no longer reflects"):
        e.hashes()


def test_tenant_namespace_routing_is_stable_and_total():
    """The r18 tenant prefix rule (`tenant/<id>/...`) is pure labeling:
    routing still keys on the FULL doc id via crc32, so namespaced ids
    place deterministically, restarts agree, and one tenant's docs
    spread across shards rather than pinning to one."""
    import zlib

    from automerge_tpu.sync import tenantledger

    ids = [f"tenant/{t}/doc{i}" for t in ("acme", "beta", "ops")
           for i in range(10)]
    e = ShardedEngineDocSet(n_shards=3)
    for did in ids:
        e.add_doc(did)
    assert sorted(e.doc_ids) == sorted(ids)
    for did in ids:
        # stable: repeat reads agree, and match the documented hash
        assert e.shard_of(did) is e.shard_of(did)
        assert e.shard_of(did) is e.shards[
            zlib.crc32(did.encode()) % e.n_shards]
    # a restart (fresh instance) routes identically — archives stay put
    e2 = ShardedEngineDocSet(n_shards=3)
    for did in ids:
        assert e.shards.index(e.shard_of(did)) == \
            e2.shards.index(e2.shard_of(did))
    # the namespace does not collapse a tenant onto one shard
    for t in ("acme", "beta", "ops"):
        shards = {e.shards.index(e.shard_of(d))
                  for d in ids if tenantledger.tenant_of(d) == t}
        assert len(shards) == e.n_shards, (t, shards)
    per = [len(s.doc_ids) for s in e.shards]
    assert sum(per) == len(ids) and all(p > 0 for p in per), per


def test_mixed_tenant_batch_coalesces_and_attributes_per_shard():
    """A mixed-tenant burst through batch() still coalesces to at most
    one dispatch per shard (tenancy never adds rounds), and the tenant
    ledger's per-shard flush rounds account every tenant's dirty docs."""
    am.metrics.reset()
    from automerge_tpu.sync import tenantledger

    e = ShardedEngineDocSet(n_shards=2)
    ids = [f"tenant/{t}/doc{i}" for t in ("acme", "beta", "ops")
           for i in range(4)]
    hashes_want = {}
    with e.batch():
        for i, did in enumerate(ids):
            chs = _mk(i)
            e.apply_changes(did, chs)
            hashes_want[did] = oracle_hash(chs)
    snap = am.metrics.snapshot()
    rounds = sum(v for k, v in snap.items()
                 if k.startswith("sync_rounds_flushed"))
    assert 1 <= rounds <= e.n_shards, snap
    h = e.hashes()
    for did, want in hashes_want.items():
        assert np.uint32(h[did]) == want, did
    sec = tenantledger.ledger().section()
    assert sec is not None
    assert set(sec["tenants"]) >= {"acme", "beta", "ops"}
    # every doc in the burst lands in exactly one tenant's round account
    assert sum(t["dirty_docs"] for t in sec["tenants"].values()) == len(ids)
    assert sec["rounds_total"] >= 1
    from automerge_tpu.perf.tenantplane import attribution_check
    chk = attribution_check(sec)
    assert chk["err_pct"] <= 1.0, chk
    am.metrics.reset()


def test_shards_bind_to_distinct_devices():
    """The module's multi-chip claim, exercised on the virtual 8-device
    CPU mesh: shards pinned round-robin over jax.devices() keep their row
    state and hash reads on THEIR device (engine/resident_rows._to_dev),
    so K shards drive K chips from one process."""
    import jax

    devs = jax.devices()[:4]
    assert len(devs) == 4   # conftest forces 8 virtual CPU devices
    e = ShardedEngineDocSet(n_shards=4, devices=devs)
    ids = [f"d{i}" for i in range(16)]
    chs = {did: _mk(i) for i, did in enumerate(ids)}
    for did in ids:
        e.apply_changes(did, chs[did])
    h = e.hashes()
    for did in ids:
        assert np.uint32(h[did]) == oracle_hash(chs[did]), did
    seen = set()
    for k, s in enumerate(e.shards):
        rset = s._resident
        assert rset.device is devs[k]
        got = set(rset.rows_dev.devices())
        assert got == {devs[k]}, (k, got)
        seen |= got
    assert len(seen) == 4   # genuinely distinct devices
