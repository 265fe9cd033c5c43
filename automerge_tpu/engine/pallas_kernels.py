"""Pallas TPU kernels for the reconcile hot loops.

The domination check at the heart of survivor analysis needs
clock(change_j)[actor_i] for every op pair (i, j) — a two-level gather in its
natural form. The MXU-friendly reformulation used here: one-hot encode each
op's actor and contract the per-op clock rows against it,

    CJI = clock_op @ onehot(actor)^T          # [N_j, N_i] via the MXU

after which domination is pure elementwise/VPU work:

    dom[j, i] = amask_j & amask_i & (fid_j == fid_i)
                & (CJI[j, i] >= seq_i) & (change_j != change_i)
    dominated[i] = any_j dom[j, i]

Clock entries are int32 sequence numbers < 2^24, exact in float32, so the
matmul runs on the systolic array at full rate.

This is an optional acceleration path: `dominated_pallas` matches the lowered
XLA computation inside kernels.field_states bit for bit (tested on TPU), and
callers fall back to the fused XLA path elsewhere. On the current single-chip
workloads the whole reconcile is transfer-bound, so this kernel is about
demonstrating and keeping open the hand-tiled path for pod-scale batches, not
about today's bench numbers.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def doc_block_spec(shape):
    """BlockSpec of one document's 2D block out of a [docs, *shape] array,
    for a grid over documents. The leading None squeezes the docs axis, so
    kernel refs are per-doc 2D and the block's last two dims EQUAL the
    array's — what the TPU lowering requires of a block that is not a
    multiple of the (8, 128) tile (a `(1, n)` block over `(docs, n)` is
    refused for docs > 1)."""
    return pl.BlockSpec((None, *shape), lambda d: (d, 0, 0),
                        memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# Fused reconcile megakernel over a docs-minor row buffer
#
# The ~60-op fused XLA reconcile is a chain of small ops and relayouts
# (reshape/transpose of [docs, small] arrays) around microseconds of
# arithmetic; what either costs on the chip is not measured. The design
# makes the *wire format* the kernel's native layout: one int32
# [ROWS, D_pad] buffer, documents minor (lane axis), every logical column
# a static row range. The whole reconcile — survivor analysis, LWW winner
# select, visibility ranks, state hash (kernels.py semantics,
# op_set.js:179-209 and 343-397 in the reference) — then runs as ONE
# pallas_call on 128-doc column blocks entirely in VMEM, with zero relayouts
# and zero glue ops.
#
# Row layout (all int32; see pack.pack_rows):
#   op_mask[I] action[I] fid[I] actor[I] seq[I] change_idx[I]
#   fid_hash[I] value_hash[I] clock_op[A*I] ins_mask[L*E] ins_fid[L*E]
#   ins_pos[L*E] elem_objhash[L*E] elem_list[L*E]
# clock_op is each op's own change-clock row, stored actor-major
# (row = a*I + i), so the kernel never indexes by change id and the change
# count C is unbounded. elem_list is the owning-list row index per element
# slot — a static iota pattern, never scattered.
#
# Every pairwise join (op x op domination, elem x op visibility,
# elem x elem rank, op x elem hash keys) is a lax.fori_loop over 8-row
# blocks of broadcasted compares: code size is O(1) in every dimension
# (no Python unrolling), per-doc dims are bounded only by VMEM, and the
# per-fid one-hots are gone entirely (fid equality is joined directly), so
# the field count F is unbounded too.
#
# The dims are the WIDEST document's, so a block of 128 small documents
# would spend most of its join on padding. Each grid step therefore reads
# its block's live extent (block_extents, computed on the device from the
# same rows) and runs its op-axis loops only to n_ops, its last row with
# op_mask set in any lane rounded up to the loop's block height, and its
# actor-band loops only to n_act, its highest actor rank of a live op + 1.
# Exact: a row at or past n_ops has op_mask 0 in every lane, so it neither
# dominates nor is dominated, is never a candidate and adds nothing to the
# hash; a band at or past n_act is selected by `actor == a`, which no live
# op meets; scratch rows the loops no longer write are read only under a
# candidate mask that is false there. Shapes stay static: the extents are
# runtime data, so no new program is compiled for them. The element loops
# stay whole (a list's slots are a prefix of its own band, not of LE).
#
# The hash must stay bit-identical to kernels.state_hash, so the murmur
# finalizer is reproduced in int32 arithmetic (wraparound add/mul and
# logical shifts give the same bits as the uint32 original).

_M1 = np.int32(np.uint32(0x85EBCA6B).astype(np.int64) - (1 << 32))
_M2 = np.int32(np.uint32(0xC2B2AE35).astype(np.int64) - (1 << 32))
_GOLD = np.int32(np.uint32(0x9E3779B9).astype(np.int64) - (1 << 32))


def _mix_i32(h):
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * _M1
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * _M2
    h = h ^ jax.lax.shift_right_logical(h, 16)
    return h


def _mix4_i32(a, b, c, d):
    h = _mix_i32(a + _GOLD)
    h = _mix_i32(h ^ b)
    h = _mix_i32(h ^ c)
    h = _mix_i32(h ^ d)
    return h


# Pairwise-join block height (sublane-aligned). 8 rows of [*, 128] int32 is
# one native TPU tile; every fori_loop below steps the j/elem axis in these
# blocks so the biggest live intermediate is 8 * max(I, LE) * 128 * 4B.
_BLK = 8


def _extents_at(ext_ref):
    """This grid step's (n_ops, n_act) out of block_extents' SMEM vector."""
    blk = pl.program_id(0)
    return ext_ref[2 * blk], ext_ref[2 * blk + 1]


def _make_reconcile_kernel(I, A, LE, a_set, a_del):
    """Build the fused kernel body for static per-doc dims.

    All joins are fori_loop-blocked broadcasted compares over the row axis;
    nothing is unrolled, so compiled code size is independent of I/A/LE and
    the per-doc field count F never appears at all.
    """
    from .pack import row_bases
    b = row_bases(I, A, LE)
    r_om, r_ac, r_fid, r_act, r_seq, r_chg, r_fh, r_vh = (
        b["om"], b["ac"], b["fid"], b["act"], b["seq"], b["chg"],
        b["fh"], b["vh"])
    r_co, r_imask, r_ifid = b["co"], b["im"], b["if"]
    r_ipos, r_iobj, r_ilist = b["ip"], b["io"], b["il"]
    r_ah = b["ah"]

    def kernel(x_ref, ext_ref, o_ref, *scratch):
        # Mosaic lowers dynamic block addressing only through refs, so every
        # blocked join reads its j/elem block from x_ref via pl.ds and
        # accumulates full-axis results either in a fori carry (pure
        # accumulation) or a VMEM scratch ref (block stores).
        n_ops, n_act = _extents_at(ext_ref)
        om = x_ref[r_om:r_om + I, :]
        action = x_ref[r_ac:r_ac + I, :]
        fid = x_ref[r_fid:r_fid + I, :]
        actor = x_ref[r_act:r_act + I, :]
        seq = x_ref[r_seq:r_seq + I, :]
        fh = x_ref[r_fh:r_fh + I, :]
        vh = x_ref[r_vh:r_vh + I, :]
        d = om.shape[1]

        amask = ((om > 0) & (action >= a_set)).astype(jnp.int32)

        # dominated[i] = any_j (amask_j & amask_i & fid_j==fid_i
        #                & clock_op[j, actor_i] >= seq_i & chg_j != chg_i)
        # j blocked in _BLK rows; the actor-axis gather becomes an inner
        # fori over A of (actor == a) selects against clock_op's a-th band.
        chg = x_ref[r_chg:r_chg + I, :]

        def dom_block(jb, dominated):
            j0 = jb * _BLK
            om_j = x_ref[pl.ds(r_om + j0, _BLK), :]
            ac_j = x_ref[pl.ds(r_ac + j0, _BLK), :]
            fid_j = x_ref[pl.ds(r_fid + j0, _BLK), :]
            chg_j = x_ref[pl.ds(r_chg + j0, _BLK), :]
            am_j = (om_j > 0) & (ac_j >= a_set)
            base = (am_j[:, None, :] & (amask[None] > 0)
                    & (fid_j[:, None, :] == fid[None])
                    & (chg_j[:, None, :] != chg[None]))

            def cp_a(a, acc):
                cja = x_ref[pl.ds(r_co + a * I + j0, _BLK), :]
                hit = ((actor[None] == a)
                       & (cja[:, None, :] >= seq[None]))
                return acc | hit.astype(jnp.int32)

            cp = jax.lax.fori_loop(
                0, n_act, cp_a, jnp.zeros((_BLK, I, d), jnp.int32))
            return dominated | jnp.any(base & (cp > 0),
                                       axis=0).astype(jnp.int32)

        dominated = jax.lax.fori_loop(
            0, n_ops // _BLK, dom_block, jnp.zeros((I, d), jnp.int32))
        survivor = (amask > 0) & (dominated == 0)
        candidate = survivor & (action != a_del)
        cand_i = candidate.astype(jnp.int32)

        if LE > 0:
            vis_ref, rank_ref, isl_ref, oh_ref, rk_ref = scratch
            imask = x_ref[r_imask:r_imask + LE, :]
            ifid = x_ref[r_ifid:r_ifid + LE, :]
            ipos = x_ref[r_ipos:r_ipos + LE, :]
            iobj = x_ref[r_iobj:r_iobj + LE, :]
            ilist = x_ref[r_ilist:r_ilist + LE, :]
            el_valid = (imask > 0) & (ifid >= 0)

            # element visible iff its field has any surviving value-carrying
            # op: a blocked elem x op join on fid equality.
            def vis_block(eb, carry):
                e0 = eb * _BLK
                ifid_b = x_ref[pl.ds(r_ifid + e0, _BLK), :]
                hit = jnp.any((ifid_b[:, None, :] == fid[None])
                              & (cand_i[None] > 0), axis=1)
                vis_ref[pl.ds(e0, _BLK), :] = hit.astype(jnp.int32)
                return carry

            jax.lax.fori_loop(0, LE // _BLK, vis_block, 0)
            elem_visible = el_valid & (vis_ref[:] > 0)
            vis_i = elem_visible.astype(jnp.int32)

            # visible rank: count of visible same-list elements with a
            # smaller RGA position (blocked elem x elem join).
            def rank_block(eb, carry):
                e0 = eb * _BLK
                pos_b = x_ref[pl.ds(r_ipos + e0, _BLK), :]
                lst_b = x_ref[pl.ds(r_ilist + e0, _BLK), :]
                cnt = jnp.sum(
                    jnp.where((lst_b[:, None, :] == ilist[None])
                              & (vis_i[None] > 0)
                              & (ipos[None] < pos_b[:, None, :]), 1, 0),
                    axis=1)
                rank_ref[pl.ds(e0, _BLK), :] = cnt
                return carry

            jax.lax.fori_loop(0, LE // _BLK, rank_block, 0)
            vis_rank = jnp.where(elem_visible, rank_ref[:], -1)

            # op -> (is_list, owning-object hash, visible rank): a blocked
            # op x elem join on fid equality.
            def opmap_block(jb, carry):
                j0 = jb * _BLK
                fid_b = x_ref[pl.ds(r_fid + j0, _BLK), :]
                m = (fid_b[:, None, :] == ifid[None]) & el_valid[None]
                isl_ref[pl.ds(j0, _BLK), :] = \
                    jnp.any(m, axis=1).astype(jnp.int32)
                oh_ref[pl.ds(j0, _BLK), :] = \
                    jnp.max(jnp.where(m, iobj[None], -1), axis=1)
                rk_ref[pl.ds(j0, _BLK), :] = \
                    jnp.max(jnp.where(m, vis_rank[None], -1), axis=1)
                return carry

            jax.lax.fori_loop(0, n_ops // _BLK, opmap_block, 0)
            op_is_list = isl_ref[:]
            key1 = jnp.where(op_is_list > 0, oh_ref[:], jnp.int32(-7))
            key2 = jnp.where(op_is_list > 0, rk_ref[:], fh)
        else:
            key1 = jnp.full_like(fh, -7)
            key2 = fh

        # per-op actor CONTENT hash from the ah band (rank-basis
        # independence, kernels.state_hash): fori over A of rank selects
        def ah_fold(a, acc):
            row = x_ref[pl.ds(r_ah + a, 1), :]
            return acc + jnp.where(actor == a, row, 0)

        ah_op = jax.lax.fori_loop(0, n_act, ah_fold,
                                  jnp.zeros_like(actor))
        contrib = _mix4_i32(key1, key2, ah_op, vh)
        o_ref[:] = jnp.sum(jnp.where(candidate, contrib, 0), axis=0,
                           keepdims=True)

    return kernel


def _make_reconcile_kernel_xl(I, A, LE, a_set, a_del, BI=32, BJ=32, BE=8):
    """XL variant of the reconcile kernel for per-doc dims whose pairwise
    joins would not fit VMEM with a full axis live: BOTH sides of every
    join are blocked ([BJ, BI, d] / [BE, BJ, d] intermediates instead of
    [8, I, d]), nothing full-axis is ever materialized as a value —
    per-block columns re-read from the input block and the survivor mask
    recomputed from a `dominated` scratch. Bit-identical to the base
    kernel (asserted by tests/test_pallas_kernels.py); the price is more
    loop iterations ((I/BI)*(I/BJ) instead of I/8), which is the right
    trade when the alternative is not compiling at all."""
    from .pack import row_bases
    b = row_bases(I, A, LE)
    r_om, r_ac, r_fid, r_act, r_seq, r_chg, r_fh, r_vh = (
        b["om"], b["ac"], b["fid"], b["act"], b["seq"], b["chg"],
        b["fh"], b["vh"])
    r_co, r_imask, r_ifid = b["co"], b["im"], b["if"]
    r_ipos, r_iobj, r_ilist = b["ip"], b["io"], b["il"]
    r_ah = b["ah"]

    def kernel(x_ref, ext_ref, o_ref, dom_ref, *scratch):
        d = x_ref.shape[1]
        n_ops, n_act = _extents_at(ext_ref)   # n_ops: a multiple of BI, BJ

        def amask_at(j0, n):
            om_j = x_ref[pl.ds(r_om + j0, n), :]
            ac_j = x_ref[pl.ds(r_ac + j0, n), :]
            return (om_j > 0) & (ac_j >= a_set), ac_j

        # ---- domination: (I/BI) x (I/BJ) blocked join --------------------
        def dom_iblock(ib, carry):
            i0 = ib * BI
            fid_i = x_ref[pl.ds(r_fid + i0, BI), :]
            act_i = x_ref[pl.ds(r_act + i0, BI), :]
            seq_i = x_ref[pl.ds(r_seq + i0, BI), :]
            chg_i = x_ref[pl.ds(r_chg + i0, BI), :]
            am_i, _ = amask_at(i0, BI)

            def dom_jblock(jb, acc):
                j0 = jb * BJ
                fid_j = x_ref[pl.ds(r_fid + j0, BJ), :]
                chg_j = x_ref[pl.ds(r_chg + j0, BJ), :]
                am_j, _ = amask_at(j0, BJ)
                base = (am_j[:, None, :] & am_i[None]
                        & (fid_j[:, None, :] == fid_i[None])
                        & (chg_j[:, None, :] != chg_i[None]))

                def cp_a(a, cp):
                    cja = x_ref[pl.ds(r_co + a * I + j0, BJ), :]
                    hit = ((act_i[None] == a)
                           & (cja[:, None, :] >= seq_i[None]))
                    return cp | hit.astype(jnp.int32)

                cp = jax.lax.fori_loop(
                    0, n_act, cp_a, jnp.zeros((BJ, BI, d), jnp.int32))
                return acc | jnp.any(base & (cp > 0),
                                     axis=0).astype(jnp.int32)

            dom_i = jax.lax.fori_loop(
                0, n_ops // BJ, dom_jblock, jnp.zeros((BI, d), jnp.int32))
            dom_ref[pl.ds(i0, BI), :] = dom_i
            return carry

        jax.lax.fori_loop(0, n_ops // BI, dom_iblock, 0)

        def cand_at(j0, n):
            """Surviving value-carrying ops of a block (recomputed from the
            dominated scratch — never held full-axis)."""
            am_j, ac_j = amask_at(j0, n)
            return (am_j & (dom_ref[pl.ds(j0, n), :] == 0)
                    & (ac_j != a_del))

        if LE > 0:
            vis_ref, rank_ref, isl_ref, oh_ref, rk_ref = scratch
            # ---- element visibility: (LE/BE) x (I/BJ) --------------------
            def vis_eblock(eb, carry):
                e0 = eb * BE
                ifid_b = x_ref[pl.ds(r_ifid + e0, BE), :]

                def vis_jblock(jb, acc):
                    j0 = jb * BJ
                    fid_j = x_ref[pl.ds(r_fid + j0, BJ), :]
                    cnd_j = cand_at(j0, BJ)
                    hit = jnp.any((ifid_b[:, None, :] == fid_j[None])
                                  & cnd_j[None], axis=1)
                    return acc | hit.astype(jnp.int32)

                hit = jax.lax.fori_loop(
                    0, n_ops // BJ, vis_jblock,
                    jnp.zeros((BE, d), jnp.int32))
                im_b = x_ref[pl.ds(r_imask + e0, BE), :]
                valid = (im_b > 0) & (ifid_b >= 0)
                vis_ref[pl.ds(e0, BE), :] = \
                    (valid & (hit > 0)).astype(jnp.int32)
                return carry

            jax.lax.fori_loop(0, LE // BE, vis_eblock, 0)

            # ---- visible rank: (LE/BE) x (LE/BE) -------------------------
            def rank_eblock(eb, carry):
                e0 = eb * BE
                pos_b = x_ref[pl.ds(r_ipos + e0, BE), :]
                lst_b = x_ref[pl.ds(r_ilist + e0, BE), :]

                def rank_fblock(fb, acc):
                    f0 = fb * BE
                    pos_f = x_ref[pl.ds(r_ipos + f0, BE), :]
                    lst_f = x_ref[pl.ds(r_ilist + f0, BE), :]
                    vis_f = vis_ref[pl.ds(f0, BE), :]
                    cnt = jnp.sum(
                        jnp.where((lst_b[:, None, :] == lst_f[None])
                                  & (vis_f[None] > 0)
                                  & (pos_f[None] < pos_b[:, None, :]),
                                  1, 0), axis=1)
                    return acc + cnt

                cnt = jax.lax.fori_loop(
                    0, LE // BE, rank_fblock,
                    jnp.zeros((BE, d), jnp.int32))
                rank_ref[pl.ds(e0, BE), :] = jnp.where(
                    vis_ref[pl.ds(e0, BE), :] > 0, cnt, -1)
                return carry

            jax.lax.fori_loop(0, LE // BE, rank_eblock, 0)

            # ---- op -> elem map: (I/BI) x (LE/BE) ------------------------
            def opmap_iblock(ib, carry):
                i0 = ib * BI
                fid_b = x_ref[pl.ds(r_fid + i0, BI), :]

                def opmap_eblock(eb, acc):
                    isl, oh, rk = acc
                    e0 = eb * BE
                    ifid_e = x_ref[pl.ds(r_ifid + e0, BE), :]
                    im_e = x_ref[pl.ds(r_imask + e0, BE), :]
                    iobj_e = x_ref[pl.ds(r_iobj + e0, BE), :]
                    valid = (im_e > 0) & (ifid_e >= 0)
                    m = (fid_b[:, None, :] == ifid_e[None]) & valid[None]
                    isl = isl | jnp.any(m, axis=1).astype(jnp.int32)
                    oh = jnp.maximum(
                        oh, jnp.max(jnp.where(m, iobj_e[None], -1), axis=1))
                    rk = jnp.maximum(
                        rk, jnp.max(jnp.where(
                            m, rank_ref[pl.ds(e0, BE), :][None], -1),
                            axis=1))
                    return (isl, oh, rk)

                z = jnp.zeros((BI, d), jnp.int32)
                isl, oh, rk = jax.lax.fori_loop(
                    0, LE // BE, opmap_eblock,
                    (z, z - 1, z - 1))
                isl_ref[pl.ds(i0, BI), :] = isl
                oh_ref[pl.ds(i0, BI), :] = oh
                rk_ref[pl.ds(i0, BI), :] = rk
                return carry

            jax.lax.fori_loop(0, n_ops // BI, opmap_iblock, 0)

        # ---- hash contribution, blocked accumulation ---------------------
        def hash_iblock(ib, acc):
            i0 = ib * BI
            fh_b = x_ref[pl.ds(r_fh + i0, BI), :]
            vh_b = x_ref[pl.ds(r_vh + i0, BI), :]
            act_b = x_ref[pl.ds(r_act + i0, BI), :]
            cnd = cand_at(i0, BI)
            if LE > 0:
                isl = isl_ref[pl.ds(i0, BI), :]
                key1 = jnp.where(isl > 0, oh_ref[pl.ds(i0, BI), :],
                                 jnp.int32(-7))
                key2 = jnp.where(isl > 0, rk_ref[pl.ds(i0, BI), :], fh_b)
            else:
                key1 = jnp.full_like(fh_b, -7)
                key2 = fh_b

            # actor CONTENT hash lookup (rank-basis independence)
            def ah_fold(a, ah_acc):
                row = x_ref[pl.ds(r_ah + a, 1), :]
                return ah_acc + jnp.where(act_b == a, row, 0)

            ah_b = jax.lax.fori_loop(0, n_act, ah_fold,
                                     jnp.zeros_like(act_b))
            contrib = _mix4_i32(key1, key2, ah_b, vh_b)
            return acc + jnp.sum(jnp.where(cnd, contrib, 0), axis=0,
                                 keepdims=True)

        o_ref[:] = jax.lax.fori_loop(
            0, n_ops // BI, hash_iblock, jnp.zeros((1, d), jnp.int32))

    return kernel


# XL-kernel block sizes and its VMEM model: the input block plus the
# dominated/vis/rank/op-map scratches plus [BJ, BI, 128]-sized live join
# intermediates — no term scales with I*8 anymore.
_XL_BI = 32
_XL_BJ = 32


def rows_dims_eligible_xl(i: int, a: int, le: int) -> bool:
    from .pack import ROWS_VMEM_BUDGET, rows_count
    # live [BJ, BI, 128] int32 join intermediates = BI*BJ [1,128]-row units
    # each (same unit convention as pack.rows_dims_eligible), three live
    inter = 3 * _XL_BI * _XL_BJ
    working = rows_count(i, a, le) + inter + 4 * i + 2 * le
    return (i % _XL_BI == 0 and (le % 8 == 0)
            and working <= ROWS_VMEM_BUDGET)


# Scoped VMEM the megakernel may use. The compiler's default (16 MiB on the
# v5e) holds the eligible working set of pack.rows_dims_eligible once; but a
# grid of more than one step double-buffers the input block, and at I=512
# the chip's compiler then refuses the kernel for any buffer wider than 128
# lanes (17.3 MiB wanted at dims (512, 4, 32) — tests/test_chip_compile.py
# holds the shape). The v5e has 128 MiB of VMEM; half of it is asked for.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _takes_xl(dims: tuple, force_xl: bool) -> bool:
    """Whether reconcile_rows_hash runs the XL variant for static `dims`;
    raises on dims that the blocked joins cannot step through."""
    I, A, LE = dims[:3]
    if I % _BLK or LE % _BLK:
        # The blocked joins step in _BLK-row tiles with no tail handling; an
        # unpadded dim would silently drop ops/elements from the joins and
        # return a WRONG hash. In-repo producers pad via encode._pad_to.
        raise ValueError(
            f"megakernel dims must be multiples of {_BLK}: I={I}, LE={LE} "
            f"(pad ops/elements before packing)")
    from .pack import rows_dims_eligible
    if rows_dims_eligible(I, A, LE) and not force_xl:
        return False
    # base working set would blow VMEM (live [8, I, d] intermediates):
    # the doubly-blocked XL kernel, dominated mask in scratch
    if I % _XL_BI:
        raise ValueError(f"XL kernel needs I % {_XL_BI} == 0, I={I}")
    return True


def _extent_step(xl: bool) -> int:
    """The block height n_ops is rounded up to: every op-axis loop of the
    variant steps by a divisor of it."""
    return math.lcm(_XL_BI, _XL_BJ) if xl else _BLK


def block_extents(rows, dims: tuple, force_xl: bool = False):
    """The live extent of each 128-lane block of `rows`, as the kernel
    reads it: int32 [2 * blocks] of (n_ops, n_act) pairs. n_ops is the
    block's last row with op_mask set in any lane, plus one, rounded up to
    the variant's block height; n_act is the highest actor rank of a live
    op in the block, plus one. One small reduction over two bands."""
    from .pack import row_bases
    I, A = dims[:2]
    b = row_bases(*dims[:3])
    step = _extent_step(_takes_xl(dims, force_xl))
    nb = rows.shape[1] // 128
    live = (rows[b["om"]:b["om"] + I] > 0).reshape(I, nb, 128)
    row = jnp.arange(1, I + 1, dtype=jnp.int32)[:, None, None]
    last = jnp.max(jnp.where(live, row, 0), axis=(0, 2))
    act = rows[b["act"]:b["act"] + I].reshape(I, nb, 128)
    n_act = jnp.max(jnp.where(live, act + 1, 0), axis=(0, 2))
    return jnp.stack([_round_up(last, step), jnp.clip(n_act, 0, A)],
                     axis=1).reshape(-1)


def host_block_extents(ops, actors, dims: tuple,
                       force_xl: bool = False) -> np.ndarray:
    """block_extents from the host's own account of the lanes, with no
    readback: `ops` and `actors` give each lane's op rows in use and its
    document's actor count. Equal to block_extents where every op row in
    use is live and every actor holds an op."""
    I, A = dims[:2]
    step = _extent_step(_takes_xl(dims, force_xl))
    ops = np.minimum(np.asarray(ops, np.int64), I).reshape(-1, 128)
    act = np.where(ops > 0, np.asarray(actors, np.int64).reshape(-1, 128), 0)
    return np.stack([_round_up(ops.max(axis=1), step),
                     np.minimum(act.max(axis=1), A)],
                    axis=1).reshape(-1).astype(np.int32)


def join_steps(ext, dims: tuple, force_xl: bool = False) -> tuple[int, int]:
    """(run, full): the actor-band trips of the domination join that the
    kernel runs over the blocks of `ext` (block_extents' layout), and
    those the static dims would run. A trip compares one block of ops
    against the block's i side in one band."""
    I, A = dims[:2]
    ext = np.asarray(ext, np.int64)
    n_ops, n_act = ext[0::2], ext[1::2]
    if _takes_xl(dims, force_xl):
        run = (n_ops // _XL_BI) * (n_ops // _XL_BJ) * n_act
        full = (I // _XL_BI) * (I // _XL_BJ) * A
    else:
        run, full = (n_ops // _BLK) * n_act, (I // _BLK) * A
    return int(run.sum()), int(full * len(n_ops))


def _rows_hash_call(rows, ext, dims: tuple, interpret: bool, xl: bool):
    """The kernel's pallas_call over `rows` with the block extents `ext`
    (block_extents' layout) in SMEM; `rows` stays the first operand."""
    I, A, LE, a_set, a_del = dims
    rows_n, d_pad = rows.shape
    if xl:
        kernel = _make_reconcile_kernel_xl(I, A, LE, a_set, a_del,
                                           _XL_BI, _XL_BJ)
        scratch = [pltpu.VMEM((I, 128), jnp.int32)]    # dominated
    else:
        kernel = _make_reconcile_kernel(I, A, LE, a_set, a_del)
        scratch = []
    if LE > 0:
        scratch += [pltpu.VMEM((LE, 128), jnp.int32),  # elem visibility
                    pltpu.VMEM((LE, 128), jnp.int32),  # elem rank
                    pltpu.VMEM((I, 128), jnp.int32),   # op is-list
                    pltpu.VMEM((I, 128), jnp.int32),   # op objhash
                    pltpu.VMEM((I, 128), jnp.int32)]   # op rank
    out = pl.pallas_call(
        kernel,
        grid=(d_pad // 128,),
        in_specs=[pl.BlockSpec((rows_n, 128), lambda d: (0, d),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, 128), lambda d: (0, d),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(rows, ext)
    return jax.lax.bitcast_convert_type(out[0], jnp.uint32)


@functools.partial(jax.jit,
                   static_argnames=("dims", "interpret", "force_xl"))
def reconcile_rows_hash(rows, dims: tuple, interpret: bool = False,
                        force_xl: bool = False):
    """Fused reconcile + state hash over a docs-minor row buffer.

    rows: [ROWS, D_pad] int32 (see pack.pack_rows); dims is the static
    (I, A, LE, a_set, a_del) tuple. Returns [D_pad] uint32 per-doc
    state hashes, bit-identical to kernels.apply_doc(...)["hash"].

    The live extent of each 128-lane block (block_extents) is computed
    here, on the device, from the rows themselves, so every caller gets
    it and nothing from the host can disagree with the rows. Each block's
    domination join then runs only to its fullest lane's last live op and
    its highest actor rank, not to the dims the widest document set: exact,
    since the rows and bands past them are masked out of every join (the
    comment above _make_reconcile_kernel says why). The extents are data,
    not static arguments: a buffer compiles once a shape, as before.
    """
    xl = _takes_xl(dims, force_xl)
    return _rows_hash_call(rows, block_extents(rows, dims, force_xl), dims,
                           interpret, xl)


# ---------------------------------------------------------------------------
# Lane gather out of a resident row buffer
#
# `rows[:, sel]` as XLA lowers it for the chip copies the whole buffer into
# a lanes-major layout first (255 MB of temporaries for the 10K fleet). Here
# the buffer is read by 128-lane blocks in place: `sel` ascends, so an
# output block draws from a run of consecutive input blocks, and all the
# (input block, output block) pairs of a call are at most one more than the
# blocks of both sides together. The grid walks the pairs; a step selects,
# inside one vreg row, the lanes of its input block that the output block
# wants and leaves the others as earlier steps wrote them.


def lane_gather_plan(sel: np.ndarray, n_pad: int) -> np.ndarray:
    """The host half of `gather_lanes`: `sel` (ascending lane indices, a
    multiple of 128 of them) followed by the pairs' input blocks and their
    output blocks, one int32 vector and so one upload. The pair count is
    padded to the static `n_pad // 128 + len(sel) // 128` by repeating the
    last pair (a repeated step fetches nothing and writes the same
    lanes)."""
    nb_in, nb_out = n_pad // 128, len(sel) // 128
    sel = np.asarray(sel, np.int64)
    pairs = np.unique(np.repeat(np.arange(nb_out), 128) * nb_in + sel // 128)
    steps = nb_in + nb_out
    pairs = np.concatenate(
        [pairs, np.full(steps - len(pairs), pairs[-1], np.int64)])
    return np.concatenate([sel, pairs % nb_in, pairs // nb_in]
                          ).astype(np.int32)


def _gather_lanes_kernel(blk_in_ref, blk_out_ref, rows_ref, sel_ref,
                         out_ref):
    del blk_out_ref     # read by the block specs alone
    local = sel_ref[...] - blk_in_ref[pl.program_id(0)] * 128     # [1, 128]
    mine = (local >= 0) & (local < 128)
    x = rows_ref[...]
    picked = jnp.take_along_axis(
        x, jnp.broadcast_to(jnp.clip(local, 0, 127), x.shape), axis=1)
    out_ref[...] = jnp.where(mine, picked, out_ref[...])


@functools.partial(jax.jit, static_argnames=("k_pad", "interpret"))
def gather_lanes(rows, plan, k_pad: int, interpret: bool = False):
    """`rows[:, sel]` for the `sel` of `plan = lane_gather_plan(sel,
    rows.shape[1])`: [ROWS, k_pad] int32, with no temporary beside the
    output. Every output lane is written by the one pair that holds its
    input block, so the buffer the first step finds needs no clearing."""
    rows_n, n_pad = rows.shape
    steps = n_pad // 128 + k_pad // 128
    sel = plan[:k_pad].reshape(1, k_pad)
    blk_in, blk_out = plan[k_pad:k_pad + steps], plan[k_pad + steps:]
    return pl.pallas_call(
        _gather_lanes_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((rows_n, 128), lambda p, bi, bo: (0, bi[p])),
                pl.BlockSpec((1, 128), lambda p, bi, bo: (0, bo[p]))],
            out_specs=pl.BlockSpec((rows_n, 128),
                                   lambda p, bi, bo: (0, bo[p]))),
        out_shape=jax.ShapeDtypeStruct((rows_n, k_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(blk_in, blk_out, rows, sel)


def _dom_kernel(clockop_ref, actor_ref, fid_ref, seq_ref, change_ref,
                amask_ref, out_ref):
    """One document: full-block domination compute in VMEM."""
    # One-hot built in-kernel from the int32 actor row (a VPU compare) so the
    # [N, A] float matrix never hits HBM; padded rows (actor = -1) are zero.
    a_pad = clockop_ref.shape[1]
    n_pad = actor_ref.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n_pad, a_pad), 1)
              == actor_ref[:].T).astype(jnp.float32)
    # CJI[j, i] = clock of op j's change, evaluated at op i's actor.
    # Precision.HIGHEST keeps the f32 operands exact on the MXU (default
    # single-pass bf16 would truncate clock values above 2^8).
    cji = jnp.dot(clockop_ref[:], onehot.T,
                  preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)

    fid = fid_ref[:]          # (1, N)
    seq = seq_ref[:]          # (1, N)
    change = change_ref[:]    # (1, N)
    amask = amask_ref[:]      # (1, N)

    fid_eq = fid.T == fid                       # [N, N] (j rows, i cols)
    mask2d = (amask.T > 0) & (amask > 0)
    not_same_change = change.T != change
    dom = mask2d & fid_eq & not_same_change & (cji >= seq)
    out_ref[:] = jnp.any(dom, axis=0, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dominated_pallas(clock_op, actor, fid, seq, change_idx, amask,
                     interpret: bool = False):
    """Per-op dominated flags for a batch of documents.

    clock_op: [docs, N, A] int32 — each op's change clock row
    actor/fid/seq/change_idx: [docs, N] int32; amask: [docs, N] bool
    Returns [docs, N] bool. `interpret=True` runs the kernel in the pallas
    interpreter (for CPU test runs).
    """
    docs, n, a = clock_op.shape
    n_pad = _round_up(max(n, 128), 128)
    a_pad = _round_up(max(a, 128), 128)

    def pad2(x, rows, fill):
        return jnp.pad(x, ((0, 0), (0, rows - x.shape[1])),
                       constant_values=fill)

    clockop_f = jnp.pad(
        clock_op.astype(jnp.float32),
        ((0, 0), (0, n_pad - n), (0, a_pad - a)))
    actor_p = pad2(actor, n_pad, -1)[:, None, :]
    fid_p = pad2(fid, n_pad, -1)[:, None, :]
    seq_p = pad2(seq, n_pad, 1 << 30)[:, None, :].astype(jnp.float32)
    change_p = pad2(change_idx, n_pad, -1)[:, None, :]
    amask_p = pad2(amask.astype(jnp.int32), n_pad, 0)[:, None, :]

    spec = doc_block_spec
    out = pl.pallas_call(
        _dom_kernel,
        grid=(docs,),
        in_specs=[
            spec((n_pad, a_pad)),   # clockop
            spec((1, n_pad)),       # actor
            spec((1, n_pad)),       # fid
            spec((1, n_pad)),       # seq
            spec((1, n_pad)),       # change
            spec((1, n_pad)),       # amask
        ],
        out_specs=spec((1, n_pad)),
        out_shape=jax.ShapeDtypeStruct((docs, 1, n_pad), jnp.int32),
        interpret=interpret,
    )(clockop_f, actor_p, fid_p, seq_p, change_p, amask_p)

    return out[:, 0, :n].astype(bool)
