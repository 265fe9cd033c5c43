"""Batched span-table merge kernels: replay only the concurrent spans.

The host text-merge plane (core/textspans.py) places one document's
concurrent runs with sequential walks — the right tool for a single
interactive document. A fleet merging MANY divergent text documents at
once (the sync service's steady state) wants the batched formulation:
every document's merge working set is a span table (engine/pack.pack_spans
— base spans of the touched regions plus the concurrent spans of both
histories, NEVER the whole document), and the merge itself is a sort:

    order   = lexsort(slot, -prio_elem, -prio_actor, block_seq)
    starts  = exclusive_cumsum(vis_len[order])     # visible positions
    hash    = sum mix4(origin, start_id, vis_len, start)   # per doc

`slot` interleaves concurrent spans into the gaps of the common history
and (prio_elem, prio_actor) DESCENDING is the RGA sibling rule
(op_set.js:343-362), so the sorted order IS the merged document order at
span granularity — cost scales with the number of concurrent spans, not
with document length. The kernel never sees per-character data.

Three implementations, parity-pinned against each other
(tests/test_textspans.py):

- `merge_spans`      — jitted XLA (vmap over the doc axis), the product
                       device path;
- `merge_spans_host` — numpy, the host fallback the adaptive router
                       (engine/dispatch.plan_spans) picks for small
                       batches, and the parity oracle;
- `span_rank_hash_pallas` — the hand-tiled rank+hash stage over
                       PRE-SORTED span lanes (the sort stays in XLA; a
                       VMEM-resident bitonic sort is not worth its code
                       size at these span counts). Optional acceleration
                       path in the dominated_pallas mold: interpret-mode
                       parity on CPU, compiled for the chip at D > 1
                       (tests/test_chip_compile.py); unrouted.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .kernels import _mix4
from .pack import SPAN_FIELDS, pack_spans  # noqa: F401  (re-export)

from jax.experimental import pallas as pl

INT32_MAX = jnp.iinfo(jnp.int32).max

F_MASK, F_ORIGIN, F_START, F_VIS, F_SLOT, F_PELEM, F_PACTOR, F_SEQ = \
    range(len(SPAN_FIELDS))


def _merge_one(rows):
    """One document's span merge: rows is [len(SPAN_FIELDS), S_pad]."""
    mask = rows[F_MASK] > 0
    slot = jnp.where(mask, rows[F_SLOT], INT32_MAX)
    order = jnp.lexsort((rows[F_SEQ], -rows[F_PACTOR], -rows[F_PELEM], slot))
    vis = jnp.where(mask, rows[F_VIS], 0)
    vis_o = vis[order]
    starts_o = jnp.cumsum(vis_o) - vis_o
    starts = jnp.zeros_like(starts_o).at[order].set(starts_o)
    contrib = _mix4(rows[F_ORIGIN], rows[F_START], vis, starts)
    h = jnp.sum(jnp.where(mask, contrib, jnp.uint32(0)), dtype=jnp.uint32)
    return order, starts, jnp.sum(vis), h


@jax.jit
def merge_spans(spans):
    """Merge a batch of span tables. spans: [D, F, S_pad] int32
    (pack.pack_spans). Returns dict of device arrays:
    order [D, S_pad] (merged position -> span slot), start [D, S_pad]
    (per-span visible start position, slot-indexed), total [D] visible
    lengths, hash [D] uint32 span-table hashes."""
    order, starts, total, h = jax.vmap(_merge_one)(spans)
    return {"order": order, "start": starts, "total": total, "hash": h}


def _mix_np(h):
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def _mix4_np(a, b, c, d):
    h = _mix_np(a.astype(np.uint32) + np.uint32(0x9E3779B9))
    h = _mix_np(h ^ b.astype(np.uint32))
    h = _mix_np(h ^ c.astype(np.uint32))
    h = _mix_np(h ^ d.astype(np.uint32))
    return h


def merge_spans_host(spans: np.ndarray) -> dict:
    """numpy reference/fallback with merge_spans's exact contract."""
    spans = np.asarray(spans, np.int32)
    mask = spans[:, F_MASK] > 0
    slot = np.where(mask, spans[:, F_SLOT], np.iinfo(np.int32).max)
    order = np.lexsort((spans[:, F_SEQ], -spans[:, F_PACTOR],
                        -spans[:, F_PELEM], slot), axis=-1).astype(np.int32)
    vis = np.where(mask, spans[:, F_VIS], 0)
    vis_o = np.take_along_axis(vis, order, axis=-1)
    starts_o = np.cumsum(vis_o, axis=-1) - vis_o
    starts = np.zeros_like(starts_o)
    np.put_along_axis(starts, order, starts_o, axis=-1)
    with np.errstate(over="ignore"):
        contrib = _mix4_np(spans[:, F_ORIGIN], spans[:, F_START], vis,
                           starts)
        h = np.where(mask, contrib, np.uint32(0)).astype(np.uint64) \
            .sum(axis=-1).astype(np.uint32)
    return {"order": order, "start": starts.astype(np.int32),
            "total": vis.sum(axis=-1).astype(np.int32), "hash": h}


def sort_spans(spans):
    """Apply the merge order on the host: [D, F, S_pad] -> rows reordered
    along the span axis (mask row included), feeding the pallas rank+hash
    stage. Kept in numpy — the sort keys are tiny next to the rank/hash
    arithmetic the kernel owns."""
    spans = np.asarray(spans, np.int32)
    mask = spans[:, F_MASK] > 0
    slot = np.where(mask, spans[:, F_SLOT], np.iinfo(np.int32).max)
    order = np.lexsort((spans[:, F_SEQ], -spans[:, F_PACTOR],
                        -spans[:, F_PELEM], slot), axis=-1)
    return np.take_along_axis(spans, order[:, None, :], axis=-1), order


# ---------------------------------------------------------------------------
# Pallas variant: rank + hash over pre-sorted span lanes

# int32 wraparound murmur finalizer — the ONE definition lives in
# pallas_kernels
from .pallas_kernels import _mix4_i32, doc_block_spec  # noqa: E402


def _rank_hash_kernel(s_pad: int):
    def kernel(x_ref, starts_ref, agg_ref):
        rows = x_ref[:]                       # [F, S_pad]
        mask = rows[F_MASK:F_MASK + 1, :] > 0         # [1, S]
        vis = jnp.where(mask, rows[F_VIS:F_VIS + 1, :], 0)
        # exclusive prefix sum along the lane axis by doubling: log2(S)
        # static shift-adds, all shapes static (S_pad is a power-of-128
        # multiple, but any static length works)
        acc = vis
        k = 1
        while k < s_pad:
            shifted = jnp.concatenate(
                [jnp.zeros((1, k), jnp.int32), acc[:, :-k]], axis=1)
            acc = acc + shifted
            k *= 2
        starts = jnp.where(mask, acc - vis, 0)    # exclusive
        starts_ref[:] = starts
        contrib = _mix4_i32(rows[F_ORIGIN:F_ORIGIN + 1, :],
                            rows[F_START:F_START + 1, :], vis, starts)
        h = jnp.sum(jnp.where(mask, contrib, 0))
        total = jnp.sum(vis)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        agg_ref[:] = jnp.where(lane == 0, h,
                               jnp.where(lane == 1, total, 0))
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def span_rank_hash_pallas(sorted_spans, interpret: bool | None = None):
    """Rank + hash over PRE-SORTED span lanes (sort_spans), one grid step
    per document, the whole table VMEM-resident. Returns (starts
    [D, S_pad] int32 in MERGED order, hash [D] uint32, total [D] int32).
    Matches merge_spans bit for bit on the hash (tests pin it in
    interpret mode; chip_smoke.py's parity stage runs it on the chip)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, f, s_pad = sorted_spans.shape
    spec = doc_block_spec
    starts, agg = pl.pallas_call(
        _rank_hash_kernel(s_pad),
        grid=(d,),
        in_specs=[spec((f, s_pad))],
        out_specs=[spec((1, s_pad)), spec((1, 128))],
        out_shape=[jax.ShapeDtypeStruct((d, 1, s_pad), jnp.int32),
                   jax.ShapeDtypeStruct((d, 1, 128), jnp.int32)],
        interpret=interpret,
    )(sorted_spans)
    return (starts[:, 0],
            jax.lax.bitcast_convert_type(agg[:, 0, 0], jnp.uint32),
            agg[:, 0, 1])
