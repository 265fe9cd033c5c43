"""TPU-readiness: run the device execution paths in pallas INTERPRET mode
on the EXACT shapes bench.py ships to the chip — including the compact
byte wire's device-side widen, the field-sharded virtual-doc split for
configs that exceed per-doc budgets, and hash recombination. With these
pinned, the only layer left untested before a hardware run is the mosaic
compiler itself, which tests/test_chip_compile.py covers for the served
path (the r5 restart lost its one chip window to a fault on these very
paths with no prior interpret-mode coverage of the bench's shapes)."""

import numpy as np
import pytest

import bench
from automerge_tpu.engine.encode import encode_doc, stack_docs
from automerge_tpu.engine.pack import (apply_rows_hash,
                                       apply_rows_hash_bytes, pack_rows,
                                       pack_rows_bytes, recombine_hashes,
                                       rows_eligible, select_field_sharding)


@pytest.fixture(scope="module", autouse=True)
def _load():
    bench._load_package()


def _batch_for(gen, n=None):
    dc = gen() if n is None else gen(n)
    actors = sorted({c.actor for chs in dc for c in chs})
    batch = stack_docs([encode_doc(chs, actors) for chs in dc])
    mf = batch.pop("max_fids")
    return dc, batch, int(mf)


def _oracle_hashes(dc):
    from automerge_tpu.engine.batchdoc import apply_batch
    _, _, out = apply_batch([chs for chs in dc])
    return np.asarray(out["hash"])[:len(dc)].astype(np.uint32)


def _rows_hashes_bytes(batch, mf, n_docs):
    import jax.numpy as jnp
    wire, bmeta, dims, n = pack_rows_bytes(batch, mf)
    assert n == n_docs, "pack_rows_bytes doc count drifted from the batch"
    got = np.asarray(apply_rows_hash_bytes.__wrapped__(
        jnp.asarray(wire), bmeta, dims, True))
    # cross-check vs the wide int32 path, exactly like bench's warmup
    rows_wide, dims_w, _ = pack_rows(batch, mf)
    want = np.asarray(apply_rows_hash(
        jnp.asarray(rows_wide), dims_w, n, interpret=True))
    assert (got[:n] == want[:n]).all(), "compact wire vs wide path mismatch"
    return got


def test_cfg2_trellis_rows_path_interpret():
    """Config 2 is rows-eligible directly: compact byte wire + megakernel
    + wide-path cross-check on the true bench batch."""
    dc, batch, mf = _batch_for(bench.gen_trellis)
    assert rows_eligible(batch, mf)
    got = _rows_hashes_bytes(batch, mf, len(dc))
    assert (got[:len(dc)] == _oracle_hashes(dc)).all()


def test_cfg1_lww_storm_field_sharded_interpret():
    """Config 1 exceeds the per-doc op budget and takes the field-sharding
    branch on TPU: virtual docs must recombine to the real docs' hashes
    (the exact code path bench.run_engine exercises on hardware)."""
    dc, batch, mf = _batch_for(bench.gen_lww_storm)
    assert not rows_eligible(batch, mf)
    sharded, owner, _target = select_field_sharding(batch, mf)
    assert sharded is not None, "field sharding found no eligible split"
    got = _rows_hashes_bytes(sharded, mf, len(owner))
    real = recombine_hashes(got, owner, len(dc))
    assert (np.asarray(real) == _oracle_hashes(dc)).all()


@pytest.mark.parametrize("gen", [bench.gen_text_trace,
                                 bench.gen_tombstone_list])
def test_cfg3_cfg4_rows_path_interpret(gen):
    dc, batch, mf = _batch_for(gen)
    if not rows_eligible(batch, mf):
        pytest.skip("shape not rows-eligible on this build")
    got = _rows_hashes_bytes(batch, mf, len(dc))
    assert (got[:len(dc)] == _oracle_hashes(dc)).all()


@pytest.mark.parametrize("gen", [bench.gen_lww_storm, bench.gen_trellis])
def test_dense_kernel_parity_on_bench_shapes(gen):
    """The EXPERIMENTAL dense one-hot formulation (demoted out of the
    product dispatch in r6 — engine/experimental_dense.py; never
    hardware-run, prime suspect in the r5 chip fault) must still agree
    with the shipped segment path on the exact bench batches a hardware
    validation session would A/B."""
    from automerge_tpu.engine import experimental_dense as xd
    from automerge_tpu.engine import kernels

    dc, batch, mf = _batch_for(gen)
    assert xd.dense_cost(batch, mf) <= xd.DENSE_BUDGET
    seg = np.asarray(kernels.apply_doc(batch, mf)["hash"])
    den = np.asarray(xd.reconcile_dense(batch, mf)["hash"])
    assert (seg == den).all()
    assert (seg[:len(dc)].astype(np.uint32) == _oracle_hashes(dc)).all()


def test_cfg5_subset_rows_path_interpret():
    """A 256-doc slice of the config-5 DocSet batch through the byte wire
    (the full 10K-doc batch in interpret mode would take minutes)."""
    dc, batch, mf = _batch_for(bench.gen_docset, 256)
    assert rows_eligible(batch, mf)
    got = _rows_hashes_bytes(batch, mf, len(dc))
    assert (got[:len(dc)] == _oracle_hashes(dc)).all()
