"""Batched move cycle-resolution kernels: log-depth pointer doubling.

The host move plane (core/moves.py) resolves one realm's winner+cycle
fixpoint with sequential ancestor walks — O(moved * depth) per admission,
the right tool for interactive single moves. A fleet absorbing a storm of
concurrent reparents (the sync service's steady state, bench config 16)
wants the batched formulation over the packed lane layout
(engine/pack.pack_moves):

    winner(i)   = cand[off_i + ptr_i]            (one gather)
    root-find   = pointer doubling, log2(N) steps, propagating the
                  MINIMUM (prio_hi, prio_lo) edge label along the walk
    drop(i)     = on-a-cycle(i)  &  e(i) == cycle-minimum(anchor(i))
    repeat until no drops (each round breaks every remaining cycle)

The label trick removes any need for explicit cycle-membership: after
2^L >= N doubling steps an unresolved node's pointer lands ON its cycle,
where the propagated minimum is exactly the cycle's minimum edge
priority — and priorities are unique (pack_moves ranks (actor, moved-id)
pairs), so the drop mask picks precisely the walk implementation's
victims. Parity with `core.moves._resolve_walk` is pinned by
tests/test_moves.py.

Three implementations, the repo's standard parity-pinned triple:

- `resolve_moves_host`   — numpy, the oracle and small-batch fallback;
- `resolve_moves`        — jitted XLA (batched gathers, while_loop);
- `move_round_pallas`    — the hand-tiled ONE-ROUND kernel (gathers as
                           one-hot reductions, whole realm VMEM-resident;
                           `resolve_moves_pallas` drives it round by
                           round — loop control stays outside, like the
                           span kernels keep their sort in XLA).
                           Interpret-mode parity on CPU, compiled for the
                           chip at D > 1 (tests/test_chip_compile.py);
                           unrouted.

Every implementation returns the same schema: ``ptr`` (winner index per
node; == cand_cnt when the base edge wins), ``parent`` (the resolved
forest), ``resolved`` (False only for undroppable cycles, e.g.
pre-existing cross-links), ``dropped`` (per-doc cycle-drop count) and a
murmur-mixed ``hash`` of the resolved table for in-run parity asserts.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .pack import MOVE_PRIO_PAD, pack_moves  # noqa: F401  (re-export)

from jax.experimental import pallas as pl

from .pallas_kernels import doc_block_spec

F_MASK, F_BASE, F_OFF, F_CNT = range(4)
F_PARENT, F_HI, F_LO = range(3)

#: node-lane ceiling for the pallas round kernel: gathers lower as
#: one-hot [N, N] reductions, which must stay VMEM-resident
PALLAS_MAX_NODES = 512


def _ceil_log2(n: int) -> int:
    bits, m = 0, 1
    while m < n:
        m *= 2
        bits += 1
    return max(bits, 1)


# ---------------------------------------------------------------------------
# numpy host oracle


def _round_host(nodes, cands, ptr):
    """One fixpoint round: (parent, drop_mask, unresolved_mask)."""
    mask = nodes[:, F_MASK] > 0
    base = nodes[:, F_BASE]
    off, cnt = nodes[:, F_OFF], nodes[:, F_CNT]
    has = mask & (ptr < cnt)
    widx = np.clip(off + np.minimum(ptr, np.maximum(cnt - 1, 0)), 0,
                   cands.shape[2] - 1)
    take = np.take_along_axis
    parent = np.where(has, take(cands[:, F_PARENT], widx, 1), base)
    ehi = np.where(has, take(cands[:, F_HI], widx, 1), MOVE_PRIO_PAD)
    elo = np.where(has, take(cands[:, F_LO], widx, 1), MOVE_PRIO_PAD)
    parent = np.where(mask, parent, -1)

    p, mh, ml = parent, ehi.copy(), elo.copy()
    for _ in range(_ceil_log2(nodes.shape[2]) + 1):
        pm = p >= 0
        pi = np.clip(p, 0, None)
        nh = take(mh, pi, 1)
        nl = take(ml, pi, 1)
        less = pm & ((nh < mh) | ((nh == mh) & (nl < ml)))
        mh = np.where(less, nh, mh)
        ml = np.where(less, nl, ml)
        p = np.where(pm, take(p, pi, 1), -1)
    unresolved = p >= 0
    anchor = np.clip(p, 0, None)
    dh = take(mh, anchor, 1)
    dl = take(ml, anchor, 1)
    drop = (unresolved & has & (ehi == dh) & (elo == dl)
            & (dh != MOVE_PRIO_PAD))
    return parent, drop, unresolved


def resolve_moves_host(packed: dict) -> dict:
    """numpy reference/fallback with the kernel triple's exact contract."""
    nodes = np.asarray(packed["nodes"], np.int32)
    cands = np.asarray(packed["cands"], np.int32)
    d, _f, n_pad = nodes.shape
    ptr = np.zeros((d, n_pad), np.int32)
    dropped = np.zeros(d, np.int32)
    for _ in range(cands.shape[2] + 1):
        parent, drop, unresolved = _round_host(nodes, cands, ptr)
        if not drop.any():
            break
        ptr = ptr + drop
        dropped = dropped + drop.sum(axis=1).astype(np.int32)
    parent, _drop, unresolved = _round_host(nodes, cands, ptr)
    mask = nodes[:, F_MASK] > 0
    resolved = mask & ~unresolved
    return {"ptr": ptr, "parent": parent, "resolved": resolved,
            "dropped": dropped, "hash": _table_hash_host(nodes, parent,
                                                         ptr)}


def _mix_np(h):
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def _table_hash_host(nodes, parent, ptr):
    mask = nodes[:, F_MASK] > 0
    slot = np.broadcast_to(np.arange(nodes.shape[2], dtype=np.int32),
                           parent.shape)
    with np.errstate(over="ignore"):
        h = _mix_np(slot.astype(np.uint32) + np.uint32(0x9E3779B9))
        h = _mix_np(h ^ parent.astype(np.uint32))
        h = _mix_np(h ^ ptr.astype(np.uint32))
        return np.where(mask, h, np.uint32(0)).astype(np.uint64) \
            .sum(axis=1).astype(np.uint32)


# ---------------------------------------------------------------------------
# jitted XLA


def _round_xla(nodes, cands, ptr):
    mask = nodes[:, F_MASK] > 0
    base = nodes[:, F_BASE]
    off, cnt = nodes[:, F_OFF], nodes[:, F_CNT]
    has = mask & (ptr < cnt)
    widx = jnp.clip(off + jnp.minimum(ptr, jnp.maximum(cnt - 1, 0)), 0,
                    cands.shape[2] - 1)
    take = jnp.take_along_axis
    parent = jnp.where(has, take(cands[:, F_PARENT], widx, axis=1), base)
    ehi = jnp.where(has, take(cands[:, F_HI], widx, axis=1), MOVE_PRIO_PAD)
    elo = jnp.where(has, take(cands[:, F_LO], widx, axis=1), MOVE_PRIO_PAD)
    parent = jnp.where(mask, parent, -1)

    def dbl(carry, _):
        p, mh, ml = carry
        pm = p >= 0
        pi = jnp.maximum(p, 0)
        nh = take(mh, pi, axis=1)
        nl = take(ml, pi, axis=1)
        less = pm & ((nh < mh) | ((nh == mh) & (nl < ml)))
        mh = jnp.where(less, nh, mh)
        ml = jnp.where(less, nl, ml)
        p = jnp.where(pm, take(p, pi, axis=1), -1)
        return (p, mh, ml), None

    (p, mh, ml), _ = jax.lax.scan(
        dbl, (parent, ehi, elo), None,
        length=_ceil_log2(nodes.shape[2]) + 1)
    unresolved = p >= 0
    anchor = jnp.maximum(p, 0)
    dh = take(mh, anchor, axis=1)
    dl = take(ml, anchor, axis=1)
    drop = (unresolved & has & (ehi == dh) & (elo == dl)
            & (dh != MOVE_PRIO_PAD))
    return parent, drop, unresolved


@jax.jit
def resolve_moves(nodes, cands):
    """Batched XLA resolution. nodes [D, 4, N_pad], cands [D, 3, K_pad]
    int32 (pack_moves). Same schema as resolve_moves_host, as device
    arrays."""
    nodes = jnp.asarray(nodes, jnp.int32)
    cands = jnp.asarray(cands, jnp.int32)
    d, _f, n_pad = nodes.shape
    ptr0 = jnp.zeros((d, n_pad), jnp.int32)

    def cond(st):
        ptr, dropped, go, rounds = st
        return go & (rounds <= cands.shape[2])

    def body(st):
        ptr, dropped, _go, rounds = st
        _parent, drop, _unres = _round_xla(nodes, cands, ptr)
        any_drop = jnp.any(drop)
        return (ptr + drop.astype(jnp.int32),
                dropped + drop.sum(axis=1).astype(jnp.int32),
                any_drop, rounds + 1)

    ptr, dropped, _go, _rounds = jax.lax.while_loop(
        cond, body, (ptr0, jnp.zeros(d, jnp.int32), jnp.bool_(True),
                     jnp.int32(0)))
    parent, _drop, unresolved = _round_xla(nodes, cands, ptr)
    mask = nodes[:, F_MASK] > 0
    slot = jnp.broadcast_to(jnp.arange(n_pad, dtype=jnp.int32),
                            parent.shape)
    from .kernels import _mix
    h = _mix(slot.astype(jnp.uint32) + jnp.uint32(0x9E3779B9))
    h = _mix(h ^ parent.astype(jnp.uint32))
    h = _mix(h ^ ptr.astype(jnp.uint32))
    table_hash = jnp.sum(jnp.where(mask, h, jnp.uint32(0)),
                         axis=1, dtype=jnp.uint32)
    return {"ptr": ptr, "parent": parent, "resolved": mask & ~unresolved,
            "dropped": dropped, "hash": table_hash}


# ---------------------------------------------------------------------------
# pallas: the one-round pointer-doubling kernel
#
# Gathers lower as one-hot [N, N] reductions (TPU-friendly: compares +
# masked row-sums on the VPU, no dynamic indexing), so the whole round —
# winner gather over the candidate lanes, L doubling steps, anchor
# lookup, drop mask — is one VMEM-resident grid step per document. The
# driver below loops rounds on the host exactly like the XLA while_loop;
# each round strictly shrinks the unresolved set, and the final ptr
# state is byte-identical to the other two implementations.


def _one_hot_gather(values, idx, n):
    """values [1, N], idx [1, N] -> values[idx] with -1/oob yielding 0."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eq = cols == idx.reshape(n, 1)
    return jnp.sum(jnp.where(eq, values.reshape(1, n), 0),
                   axis=1).reshape(1, n)


def _move_round_kernel(n_pad: int, k_pad: int, steps: int):
    def kernel(nodes_ref, cands_ref, ptr_ref, out_ref):
        nodes = nodes_ref[:]             # [4, N]
        cands = cands_ref[:]             # [3, K]
        ptr = ptr_ref[:]                 # [1, N]
        mask = nodes[F_MASK:F_MASK + 1, :] > 0
        base = nodes[F_BASE:F_BASE + 1, :]
        off = nodes[F_OFF:F_OFF + 1, :]
        cnt = nodes[F_CNT:F_CNT + 1, :]
        has = mask & (ptr < cnt)
        widx = jnp.clip(off + jnp.minimum(ptr, jnp.maximum(cnt - 1, 0)),
                        0, k_pad - 1)
        # winner gather over the K axis: one-hot [N, K] reduction
        kcols = jax.lax.broadcasted_iota(jnp.int32, (n_pad, k_pad), 1)
        keq = kcols == widx.reshape(n_pad, 1)

        def kgather(row):
            return jnp.sum(jnp.where(keq, row.reshape(1, k_pad), 0),
                           axis=1).reshape(1, n_pad)

        parent = jnp.where(has, kgather(cands[F_PARENT]), base)
        ehi = jnp.where(has, kgather(cands[F_HI]), MOVE_PRIO_PAD)
        elo = jnp.where(has, kgather(cands[F_LO]), MOVE_PRIO_PAD)
        parent = jnp.where(mask, parent, -1)

        p, mh, ml = parent, ehi, elo
        for _ in range(steps):
            pm = p >= 0
            pi = jnp.maximum(p, 0)
            nh = _one_hot_gather(mh, pi, n_pad)
            nl = _one_hot_gather(ml, pi, n_pad)
            less = pm & ((nh < mh) | ((nh == mh) & (nl < ml)))
            mh = jnp.where(less, nh, mh)
            ml = jnp.where(less, nl, ml)
            p = jnp.where(pm, _one_hot_gather(p, pi, n_pad), -1)
        unresolved = p >= 0
        anchor = jnp.maximum(p, 0)
        dh = _one_hot_gather(mh, anchor, n_pad)
        dl = _one_hot_gather(ml, anchor, n_pad)
        drop = (unresolved & has & (ehi == dh) & (elo == dl)
                & (dh != MOVE_PRIO_PAD))
        # lanes: 0 = drop mask, 1 = unresolved, 2 = parent
        out = jnp.concatenate([drop.astype(jnp.int32),
                               unresolved.astype(jnp.int32),
                               parent], axis=0)
        out_ref[:] = out
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def move_round_pallas(nodes, cands, ptr, interpret: bool | None = None):
    """One fixpoint round for every document: returns [D, 3, N_pad] int32
    lanes (drop mask, unresolved mask, tentative parent)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, _f, n_pad = nodes.shape
    k_pad = cands.shape[2]
    if n_pad > PALLAS_MAX_NODES:
        raise ValueError(f"pallas move kernel caps at {PALLAS_MAX_NODES} "
                         f"node lanes (got {n_pad}); route larger realms "
                         "through resolve_moves (XLA)")
    steps = _ceil_log2(n_pad) + 1
    spec = doc_block_spec
    return pl.pallas_call(
        _move_round_kernel(n_pad, k_pad, steps),
        grid=(d,),
        in_specs=[spec((4, n_pad)), spec((3, k_pad)), spec((1, n_pad))],
        out_specs=spec((3, n_pad)),
        out_shape=jax.ShapeDtypeStruct((d, 3, n_pad), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(nodes, jnp.int32), jnp.asarray(cands, jnp.int32),
      jnp.asarray(ptr, jnp.int32)[:, None, :])


def resolve_moves_pallas(packed: dict,
                         interpret: bool | None = None) -> dict:
    """Full resolution driven through the pallas round kernel (loop
    control on the host, like the span plane keeps its sort in XLA).
    Same schema as resolve_moves_host."""
    nodes = np.asarray(packed["nodes"], np.int32)
    cands = np.asarray(packed["cands"], np.int32)
    d, _f, n_pad = nodes.shape
    ptr = np.zeros((d, n_pad), np.int32)
    dropped = np.zeros(d, np.int32)
    parent = unresolved = None
    for _ in range(cands.shape[2] + 2):
        out = np.asarray(move_round_pallas(nodes, cands, ptr,
                                           interpret=interpret))
        drop = out[:, 0] > 0
        unresolved = out[:, 1] > 0
        parent = out[:, 2]
        if not drop.any():
            break
        ptr = ptr + drop
        dropped = dropped + drop.sum(axis=1).astype(np.int32)
    mask = nodes[:, F_MASK] > 0
    return {"ptr": ptr, "parent": parent,
            "resolved": mask & ~unresolved, "dropped": dropped,
            "hash": _table_hash_host(nodes, parent, ptr)}
