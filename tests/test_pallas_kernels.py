"""Pallas domination kernel vs a direct reference computation.

Runs in the pallas interpreter on the CPU test backend; the same kernel
compiles for real on TPU (exercised by bench/driver runs there).
"""

import numpy as np
import pytest

import jax

from automerge_tpu.engine.pallas_kernels import dominated_pallas


def reference_dominated(clock_op, actor, fid, seq, change_idx, amask):
    docs, n, _ = clock_op.shape
    out = np.zeros((docs, n), dtype=bool)
    for d in range(docs):
        for i in range(n):
            if not amask[d, i]:
                continue
            for j in range(n):
                if (amask[d, j] and fid[d, j] == fid[d, i]
                        and change_idx[d, j] != change_idx[d, i]
                        and clock_op[d, j, actor[d, i]] >= seq[d, i]):
                    out[d, i] = True
                    break
    return out


def random_case(rng, docs=3, n=24, n_actors=4, n_fids=6, n_changes=8):
    clock_op = rng.integers(0, 5, size=(docs, n, n_actors)).astype(np.int32)
    actor = rng.integers(0, n_actors, size=(docs, n)).astype(np.int32)
    fid = rng.integers(0, n_fids, size=(docs, n)).astype(np.int32)
    seq = rng.integers(1, 6, size=(docs, n)).astype(np.int32)
    change_idx = rng.integers(0, n_changes, size=(docs, n)).astype(np.int32)
    amask = rng.random(size=(docs, n)) < 0.8
    return clock_op, actor, fid, seq, change_idx, amask


@pytest.mark.parametrize("seed", range(5))
def test_matches_reference(seed):
    rng = np.random.default_rng(seed)
    args = random_case(rng)
    expected = reference_dominated(*args)
    interpret = jax.default_backend() != "tpu"
    actual = np.asarray(dominated_pallas(*map(jax.numpy.asarray, args),
                                         interpret=interpret))
    np.testing.assert_array_equal(actual, expected)


def test_engine_parity_on_real_batch():
    """The pallas kernel agrees with the XLA path inside field_states on a
    real encoded document batch."""
    import automerge_tpu as am
    from automerge_tpu.engine.encode import encode_doc, stack_docs, A_SET

    s1 = am.change(am.init("A"), lambda d: am.assign(d, {"x": 1, "y": 2}))
    s2 = am.merge(am.init("B"), s1)
    s1 = am.change(s1, lambda d: d.__setitem__("x", 10))
    s2 = am.change(s2, lambda d: am.assign(d, {"x": 20, "z": 3}))
    m = am.merge(s1, s2)
    changes = m._doc.opset.get_missing_changes({})
    enc = encode_doc(changes, sorted({c.actor for c in changes}))
    batch = stack_docs([enc])
    batch.pop("max_fids")

    clock_op = batch["clock"][np.arange(1)[:, None], batch["change_idx"]]
    amask = batch["op_mask"] & (batch["action"] >= A_SET)
    interpret = jax.default_backend() != "tpu"
    dom = np.asarray(dominated_pallas(
        jax.numpy.asarray(clock_op), jax.numpy.asarray(batch["actor"]),
        jax.numpy.asarray(batch["fid"]), jax.numpy.asarray(batch["seq"]),
        jax.numpy.asarray(batch["change_idx"]), jax.numpy.asarray(amask),
        interpret=interpret))
    expected = reference_dominated(clock_op, batch["actor"], batch["fid"],
                                   batch["seq"], batch["change_idx"], amask)
    np.testing.assert_array_equal(dom, expected)


# ---------------------------------------------------------------------------
# Fused reconcile megakernel: bit-parity with the XLA apply path


def _hash_both_ways(doc_changes):
    """Return (xla_hashes, pallas_hashes) for a list of per-doc change
    lists, through the packed-XLA and docs-minor-rows paths."""
    from automerge_tpu.engine.encode import encode_doc, stack_docs
    from automerge_tpu.engine.pack import (apply_packed_hash, apply_rows_hash,
                                           pack_batch, pack_rows,
                                           rows_eligible)

    actors = sorted({c.actor for changes in doc_changes for c in changes})
    encs = [encode_doc(c, actors) for c in doc_changes]
    batch = stack_docs(encs)
    max_fids = batch.pop("max_fids")
    flat, meta = pack_batch(batch)
    ref = np.asarray(apply_packed_hash(jax.numpy.asarray(flat), meta,
                                       max_fids))
    assert rows_eligible(batch, max_fids)
    rows, dims, n = pack_rows(batch, max_fids)
    interpret = jax.default_backend() != "tpu"
    got = np.asarray(apply_rows_hash(jax.numpy.asarray(rows), dims, n,
                                     interpret=interpret))
    return ref, got


def test_reconcile_rows_map_docs():
    """Concurrent map edits across a small DocSet batch: the megakernel's
    hashes are bit-identical to the XLA path's."""
    import automerge_tpu as am

    doc_changes = []
    for i in range(7):
        s1 = am.change(am.init("A"), lambda d, i=i: am.assign(
            d, {"n": i, "tag": f"t{i % 3}", "flags": {"hot": i % 2 == 0}}))
        s2 = am.merge(am.init("B"), s1)
        s1 = am.change(s1, lambda d, i=i: d.__setitem__("n", i + 1))
        s2 = am.change(s2, lambda d, i=i: am.assign(d, {"n": -i, "o": "B"}))
        m = am.merge(s1, s2)
        doc_changes.append(m._doc.opset.get_missing_changes({}))
    ref, got = _hash_both_ways(doc_changes)
    np.testing.assert_array_equal(ref, got)


def test_reconcile_rows_lists_and_tombstones():
    """List inserts/deletes (tombstone ranks, list-element hashing) agree."""
    import automerge_tpu as am

    doc_changes = []
    for i in range(3):
        d = am.change(am.init("A"), lambda doc: doc.__setitem__("xs", []))
        for j in range(4):
            d = am.change(d, lambda doc, j=j: doc["xs"].insert_at(j, j * 10))
        d = am.change(d, lambda doc: doc["xs"].delete_at(1))
        r = am.merge(am.init("B"), d)
        r = am.change(r, lambda doc: doc["xs"].insert_at(0, 99))
        m = am.merge(d, r)
        doc_changes.append(m._doc.opset.get_missing_changes({}))
    ref, got = _hash_both_ways(doc_changes)
    np.testing.assert_array_equal(ref, got)


def test_reconcile_rows_large_dims():
    """VERDICT r1 #5 done-criterion: the blocked megakernel handles I>=256
    and F>=128 per doc (far past the old unrolled kernel's 64-caps) with
    bit-identical hashes vs the XLA path."""
    import automerge_tpu as am

    big = am.change(am.init("A"), lambda d: d.__setitem__(
        "xs", list(range(12))))
    for i in range(130):
        big = am.change(big, lambda d, i=i: d.__setitem__(f"k{i}", i))
    b2 = am.change(am.merge(am.init("B"), big),
                   lambda d: d.__setitem__("k3", -1))
    big = am.merge(big, b2)
    changes = big._doc.opset.get_missing_changes({})

    from automerge_tpu.engine.encode import encode_doc, stack_docs
    actors = sorted({c.actor for c in changes})
    batch = stack_docs([encode_doc(changes, actors)] * 2)
    max_fids = batch.pop("max_fids")
    assert batch["op_mask"].shape[1] >= 256
    assert max_fids >= 128

    ref, got = _hash_both_ways([changes] * 2)
    np.testing.assert_array_equal(ref, got)


def test_reconcile_rows_convergence_hash():
    """Two replicas that merged in opposite orders hash identically through
    the megakernel (delivery-order independence)."""
    import automerge_tpu as am

    a = am.change(am.init("A"), lambda d: am.assign(d, {"x": 1, "y": [1, 2]}))
    b = am.merge(am.init("B"), a)
    a2 = am.change(a, lambda d: d.__setitem__("x", 5))
    b2 = am.change(b, lambda d: d["y"].insert_at(0, 7))
    ab = am.merge(a2, b2)
    ba = am.merge(b2, a2)
    ref, got = _hash_both_ways([
        ab._doc.opset.get_missing_changes({}),
        ba._doc.opset.get_missing_changes({})])
    np.testing.assert_array_equal(ref, got)
    assert got[0] == got[1]


def _xl_parity(doc_changes):
    """force_xl vs base kernel: bit-identical hashes (interpret mode)."""
    import jax.numpy as jnp

    from automerge_tpu.engine.encode import encode_doc, stack_docs
    from automerge_tpu.engine.pack import pack_rows
    from automerge_tpu.engine.pallas_kernels import reconcile_rows_hash

    actors = sorted({c.actor for chs in doc_changes for c in chs})
    encs = [encode_doc(c, actors) for c in doc_changes]
    batch = stack_docs(encs)
    mf = batch.pop("max_fids")
    rows, dims, n = pack_rows(batch, mf)
    assert dims[0] % 32 == 0, f"test shape must pad I to 32: {dims}"
    interp = jax.default_backend() != "tpu"
    base = np.asarray(reconcile_rows_hash(
        jnp.asarray(rows), dims, interp, False))[:n]
    xl = np.asarray(reconcile_rows_hash(
        jnp.asarray(rows), dims, interp, True))[:n]
    np.testing.assert_array_equal(base, xl)
    return dims


def test_xl_kernel_parity_maps_and_lists():
    """The doubly-blocked XL kernel (for dims whose joins would blow VMEM
    with a full axis live) hashes bit-identically to the base kernel."""
    import automerge_tpu as am

    docs = []
    for i in range(5):
        s1 = am.change(am.init("A"), lambda d, i=i: am.assign(
            d, {"n": i, "xs": [1, 2, 3]}))
        s2 = am.merge(am.init("B"), s1)
        s1 = am.change(s1, lambda d: d["xs"].delete_at(0))
        s2 = am.change(s2, lambda d, i=i: am.assign(d, {"n": -i, "o": "B"}))
        for k in range(10):
            s1 = am.change(s1, lambda d, k=k: d.__setitem__(f"k{k}", k))
        m = am.merge(s1, s2)
        docs.append(m._doc.opset.get_missing_changes({}))
    _xl_parity(docs)


def test_xl_kernel_parity_concurrent_text():
    """Concurrent text editing (tombstones, rank shifts, 3 actors) through
    the XL kernel: the shape class config 3 batched lands in."""
    import random

    import automerge_tpu as am

    rng = random.Random(9)
    docs = []
    for _ in range(2):
        def mk(d):
            d["t"] = am.Text()
            d["t"].insert_at(0, *"hello world ok")
        base = am.change(am.init("base"), mk)
        reps = {a: am.merge(am.init(a), base) for a in "AB"}
        for step in range(30):
            a = rng.choice("AB")
            d = reps[a]
            n = len(d["t"])
            if rng.random() < 0.7 or n == 0:
                d = am.change(d, lambda x, p=rng.randint(0, n):
                              x["t"].insert_at(p, rng.choice("xyz")))
            else:
                d = am.change(d, lambda x, p=rng.randrange(n):
                              x["t"].delete_at(p))
            reps[a] = d
        m = am.merge(reps["A"], reps["B"])
        docs.append(m._doc.opset.get_missing_changes({}))
    dims = _xl_parity(docs)
    assert dims[0] >= 32 and dims[2] >= 32  # ops and elems both blocked
