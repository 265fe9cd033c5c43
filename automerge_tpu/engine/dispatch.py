"""Adaptive backend routing: host vs device, by measured cost model.

The reference has exactly one execution path (per-op interpretive JS);
this framework has three with very different cost shapes:

- host interpretive (core/opset.py): ~O(ops) with a small per-op constant —
  no fixed costs at all;
- host bulk build (core/bulkload.py): vectorized from-scratch state build,
  wins over interpretive from ~BULK_MIN_CHANGES changes per doc;
- device columnar (engine/pack.py + pallas megakernel): microseconds of
  per-doc compute, but behind fixed per-dispatch / per-transfer / per-
  readback costs of the host<->device link.

A small single document therefore *belongs on the host* wherever a link
roundtrip costs more than the job; the DocSet batch axis is where the
device path wins (128+ documents per dispatch). This module is the
product-path router that makes that call, the moral equivalent of XLA's
own host/device offload decisions. It also holds the one decision of how
a resident rows set reconciles its dirty lanes (reconcile_route): a round
asks it once, a hash read once, and the engine executes what it returns.

The cost-model constants below were not measured on a directly attached
chip: they price a dispatch at 25 ms and a readback at 70 ms, which no
chip run bears out. Re-pricing them from the chip, or deleting the
decisions one side always wins, is ROADMAP S5; calibrate() overrides them
meanwhile.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np

from ..utils import metrics, perfscope
from . import dispatchledger
from .encode import _pad_to
from .pack import (LANE, MOVE_CAND_FIELDS, MOVE_NODE_FIELDS, SPAN_FIELDS,
                   mega_row_map, pack_spans, pad_to_lanes, plan_megabuckets,
                   rows_count)

# Link cost model (seconds). Not measured on the chip (ROADMAP S5).
_LINK = {
    "dispatch_fixed_s": 0.025,   # per jitted dispatch (amortizable)
    "h2d_call_s": 0.010,         # per host->device transfer call
    "h2d_bytes_per_s": 450e6,    # below the ~24MB/call collapse point
    "d2h_call_s": 0.070,         # per readback call
    "host_op_s": 6e-6,           # no-diff interpretive per-op apply +
                                 # materialize (measured 2.9-5.8e-6 across
                                 # map/text/mixed shapes, r5)
    "bulk_op_s": 5.5e-6,         # bulk-build per-op from IN-MEMORY changes
                                 # (changes_to_columns conversion dominates;
                                 # measured 5.6-8.1e-6 at 8K-114K ops, r5.
                                 # load()-from-text is far cheaper via the
                                 # native JSON parse, but that is not the
                                 # path apply_host prices)
    "bulk_fixed_s": 0.001,
    "span_op_s": 2.5e-7,         # host numpy span-merge per span (lexsort
                                 # + cumsum + hash over packed lanes)
    "span_fixed_s": 1e-4,        # host numpy span-merge per batch (fixed
                                 # array setup)
    "move_lane_s": 6e-8,         # host numpy move-resolution per node/
                                 # cand lane per doubling round (gathers
                                 # + compares over packed lanes; measured
                                 # ~0.05-0.08us/lane on the 2-core bench
                                 # host at 128-1024 lanes)
    "move_fixed_s": 2e-4,        # host numpy move-resolution per batch
                                 # (array setup + 2 fixpoint rounds min)
}


def calibrate(**overrides) -> None:
    """Override link constants (e.g. from a deployment's own probe)."""
    for k, v in overrides.items():
        if k not in _LINK:
            raise KeyError(k)
        _LINK[k] = float(v)


# apply_host engages the vectorized bulk build above this many changes per
# document. Recalibrated for the no-diff interpretive mode (opset.
# add_changes(emit_diffs=False)): with per-op edit records and sequence-
# index upkeep gone, the interpretive path is O(ops) with one end-of-batch
# RGA linearization — the same asymptotics as bulk — and bulk's remaining
# edge is numpy constants vs the Python op loop, which only outweighs its
# changes_to_columns conversion cost on very large in-memory logs
# (measured: interp wins/ties at 45/500/2000/8000/16384 changes across
# map/text/mixed shapes; bulk wins 1.35x at 65536). load()-from-text keeps
# its own much lower threshold (64): its native JSON parse feeds columns
# directly, skipping the conversion that dominates here.
HOST_BULK_MIN_CHANGES = 24576


@dataclass
class Plan:
    backend: str          # "device" | "host"
    est_device_s: float
    est_host_s: float


def plan_batch(n_docs: int, n_ops: int, wire_bytes: int,
               passes: int = 1, changes_per_doc: float | None = None) -> Plan:
    """Choose the backend for a from-scratch batch apply of `n_docs`
    documents totalling `n_ops` ops, shipping `wire_bytes` per pass,
    with fixed costs amortized over `passes` identical jobs.

    `changes_per_doc` prices the host side with the SAME predicate
    apply_host executes (bulk build from HOST_BULK_MIN_CHANGES changes per
    doc); when unknown it is estimated at n_ops/n_docs/2 (ins+set pairs)."""
    dev = _device_cost(wire_bytes, passes)
    if changes_per_doc is None:
        changes_per_doc = n_ops / max(n_docs, 1) / 2
    if changes_per_doc >= HOST_BULK_MIN_CHANGES:
        host = n_docs * _LINK["bulk_fixed_s"] + n_ops * _LINK["bulk_op_s"]
    else:
        host = n_ops * _LINK["host_op_s"]
    backend = "device" if dev < host else "host"
    return Plan(backend, dev, host)


def _device_cost(wire_bytes: int, passes: int) -> float:
    return (_LINK["dispatch_fixed_s"] / passes
            + _LINK["h2d_call_s"]
            + wire_bytes / _LINK["h2d_bytes_per_s"]
            + _LINK["d2h_call_s"] / passes)


def plan_for(doc_changes: list, passes: int = 1) -> Plan:
    """Plan (no execution) for a concrete from-scratch batch: estimates the
    wire from the same padded dims pack.py will use, and prices the host
    side per document with apply_host's actual bulk/interpretive predicate."""
    # one fused pass per doc (this runs per ROUTED job — on a millisecond
    # single-doc apply the router's own scan is a measurable tax)
    max_ops = 1
    max_ins = 1
    actors: set = set()
    for chs in doc_changes:
        doc_ops = 0
        doc_ins = 0
        for c in chs:
            doc_ops += len(c.ops)
            for o in c.ops:
                if o.action == "ins":
                    doc_ins += 1
            actors.add(c.actor)
        if doc_ops > max_ops:
            max_ops = doc_ops
        if doc_ins > max_ins:
            max_ins = doc_ins
    ops_pad = _pad_to(max_ops)
    ins_pad = _pad_to(max_ins)
    d_pad = pad_to_lanes(len(doc_changes))  # pack.py's canonical lane pad
    wire_bytes = (rows_count(ops_pad, max(len(actors), 1), ins_pad)
                  * d_pad * 4)

    dev = _device_cost(wire_bytes, passes)
    host = 0.0
    for chs in doc_changes:
        doc_ops = sum(len(c.ops) for c in chs)
        if len(chs) >= HOST_BULK_MIN_CHANGES:  # apply_host's predicate
            host += _LINK["bulk_fixed_s"] + doc_ops * _LINK["bulk_op_s"]
        else:
            host += doc_ops * _LINK["host_op_s"]
    plan = Plan("device" if dev < host else "host", dev, host)
    # the padded dims this scan already derived, kept for the dispatch
    # ledger's padding-waste account (no second scan at the call site)
    plan.dims = {"docs": (len(doc_changes), d_pad),
                 "ops": (max_ops, ops_pad), "ins": (max_ins, ins_pad)}
    return plan


# ---------------------------------------------------------------------------
# How a resident rows set reconciles its dirty lanes. reconcile_route is
# the decision; plan_round prices the fused route for it:
# pack.plan_megabuckets quantizes the lanes' ragged doc sizes onto a small
# shape ladder and the bucketed dispatches are compared with what the
# lanes would take otherwise (the whole buffer when a majority of the
# fleet is dirty, the full-dims lane gather when not).

# A round or a read of fewer documents never plans the fused route: no
# batch of one amortizes bucket planning.
MEGABATCH_MIN_DOCS = 2

_megabatch: bool | None = None


def megabatch_enabled() -> bool:
    """AMTPU_MEGABATCH != "0" (default on). One cached check — the
    disabled path costs a single comparison per round."""
    global _megabatch
    if _megabatch is None:
        _megabatch = os.environ.get("AMTPU_MEGABATCH", "1") != "0"
    return _megabatch


def _reload_for_tests() -> None:
    global _megabatch
    _megabatch = None


@dataclass
class RoundPlan:
    route: str                      # "megabatch" | "per_doc"
    docs: list = field(default_factory=list)    # doc indices, sorted
    buckets: list = field(default_factory=list)  # pack.plan_megabuckets
    est_mega_s: float = 0.0
    est_alt_s: float = 0.0


@dataclass(frozen=True)
class Route:
    """What reconcile_route decided; ResidentRowsDocSet executes it."""
    kind: str             # deferred | blocks | whole | lanes | fused | handle
    lanes: list = field(default_factory=list)   # the lanes it reconciles
    plan: RoundPlan | None = None               # fused: its dispatches
    blocks: tuple = ()    # blocks: the dirty 128-lane blocks, padded
    # False: scatter and reconcile are one program (_apply_final) whose
    # hash vector stays on the device as the pending handle
    readback: bool = True


def share_route(rset, lanes) -> Route:
    """The minority rule: a majority of the fleet reconciles as the whole
    buffer (the lane gather would copy most of it anyway; one kernel
    shape, and the device copy is primed), a minority as gathered lanes."""
    whole = 2 * len(lanes) >= len(rset.doc_ids)
    return Route("whole" if whole else "lanes", lanes)


def _fused_route(rset, lanes) -> Route | None:
    plan = plan_round(rset, lanes)
    return Route("fused", lanes, plan) if plan.route == "megabatch" else None


def _read_route(rset, lanes) -> Route:
    minority = 2 * len(lanes) < len(rset.doc_ids)
    return (minority and _fused_route(rset, lanes)) or share_route(rset, lanes)


def _plans(lanes, round_docs: int) -> bool:
    """Whether an eager engine's round plans (the table of
    reconcile_route: two documents or more, a lane touched, megabatch
    on)."""
    return bool(megabatch_enabled() and round_docs >= MEGABATCH_MIN_DOCS
                and lanes)


def scatters_first(rset, lanes, round_docs: int) -> bool:
    """The first half of a round's decision, which needs no plan: every
    route a planning round can get (fused, lanes, read-back whole, a
    read's) first scatters the round's triplets into a current device
    copy, so the engine sends that scatter out BEFORE it asks
    reconcile_route, and the chip works while the router reads the host
    mirror. False for a round that never plans (its scatter and reconcile
    are one program), a lazy engine, and a copy that is not current (the
    column "scatters before the plan" of reconcile_route's table).
    Reads state, changes none."""
    return bool(not rset.lazy_dispatch and _plans(lanes, round_docs)
                and rset._dev_current)


@perfscope.phased("route")
def reconcile_route(rset, lanes, round_docs: int | None = None) -> Route:
    """How the dirty `lanes` (doc indices, ascending) of a resident rows
    set reconcile. The one place this is decided: a round asks once, after
    it has committed its triplets to the host mirror and marked the lanes
    they touch dirty (`lanes` are those, `round_docs` the number of
    documents its frames name); a hash read asks once (`round_docs` None,
    `lanes` the dirty ones among those asked for, at least one unless a
    handle is pending). Reads state, changes none. "Plans" below is one
    plan_round call: off under AMTPU_MEGABATCH=0 and for fewer than
    MEGABATCH_MIN_DOCS lanes, else the link cost model's verdict on the
    bucketed dispatches. n = len(rset.doc_ids). The last column is
    scatters_first, the half of the decision a round takes before it
    asks here: where it says "copy current", the round's triplets are
    already on their way into the device copy when the plan is made.

    A round:

    | engine | the round                        | observed                           | route    | scatters before the plan |
    |--------|----------------------------------|------------------------------------|----------|--------------------------|
    | lazy   | any                              |                                    | deferred | never                    |
    | eager  | one document, or no lane touched,| _h_prev valid, the dirty 128-lane  | blocks   | never: one program       |
    |        | or AMTPU_MEGABATCH=0: never plans| blocks (padded to a power of two)  |          |                          |
    |        |                                  | at most half of n_pad / 128        |          |                          |
    | eager  | the same                         | no valid _h_prev (the copy was     | whole,   | never: one program       |
    |        |                                  | uploaded or re-laid since), or the | readback |                          |
    |        |                                  | blocks are no minority             | False    |                          |
    | eager  | two documents or more: plans over| the plan fuses                     | fused    | copy current             |
    |        | its lanes whatever their share   |                                    |          |                          |
    | eager  | the same                         | declined; no other lane is dirty;  | lanes    | copy current             |
    |        |                                  | 2 * lanes < n                      |          |                          |
    | eager  | the same                         | declined; no other lane is dirty;  | whole    | copy current             |
    |        |                                  | 2 * lanes >= n                     |          |                          |
    | eager  | the same                         | declined; lanes from outside the   | a read's,| copy current             |
    |        |                                  | round are dirty too (a failed      | over all |                          |
    |        |                                  | dispatch, a deferred read)         | of them  |                          |

    A read:

    | observed                                                   | route  |
    |------------------------------------------------------------|--------|
    | a flush-time hash handle is pending and the copy current   | handle |
    | 2 * lanes < n: plans; the plan fuses                       | fused  |
    | 2 * lanes < n, declined                                    | lanes  |
    | 2 * lanes >= n: never plans                                | whole  |

    deferred: drop the copy; the next read reconciles. blocks, and whole
    with readback False: the scatter and the reconcile in one program
    (_apply_final), on the device copy (uploaded first where it is not
    current), the hash vector left there as _h_prev and the pending
    handle. fused: the plan's bucketed dispatches out of the host mirror.
    lanes: gather_lanes out of a current copy, the host gather and an
    upload where it is not, one reconcile. whole: reconcile_rows_hash
    over the copy (uploaded where not current), read back, kept as
    _h_prev. handle: one readback of the pending vector. A round's
    fused, lanes and read-back whole first scatter its triplets into the
    copy where it is current (scatters_first: before this router runs;
    one that is not current is dropped after it) and drop _h_prev and the
    handle. A round's lanes and read-back whole are dispatched and not
    read back: the vector stays with the engine as its one unsettled
    round until the collect half (ResidentRowsDocSet.collect_round).

    Where the round and the read differ: a round plans over its lanes
    even when they are a majority of the fleet, a read only for a
    minority; a round of one document never plans, a read of its lane and
    another's does (ROADMAP S5: undecided).
    """
    if round_docs is None:
        if rset._hash_handle is not None and rset._dev_current:
            return Route("handle")
        return _read_route(rset, lanes)
    if rset.lazy_dispatch:
        return Route("deferred")
    if not _plans(lanes, round_docs):
        blocks = sorted({i // LANE for i in lanes})
        nb = _pad_to(len(blocks), 1)
        if blocks and rset._h_prev is not None and rset._dev_current \
                and 2 * nb <= rset.n_pad // LANE:
            # padded by repeating the last: its hashes are written twice
            return Route("blocks", lanes, readback=False, blocks=tuple(
                blocks + blocks[-1:] * (nb - len(blocks))))
        return Route("whole", lanes, readback=False)
    fused = _fused_route(rset, lanes)
    if fused is not None:
        return fused
    n = len(rset.doc_ids)
    dirty = sorted(i for i in rset._doc_dirty if i < n)
    return share_route(rset, lanes) if dirty == lanes \
        else _read_route(rset, dirty)


def plan_round(rset, idxs) -> RoundPlan:
    """The fused route priced for the dirty docs `idxs` of a resident
    set: bucket their exact used sizes (band scans — correct across
    compaction/rebuild) and compare the fused bucketed dispatches against
    what the lanes take otherwise. Returns a RoundPlan whose buckets are
    the offset tables apply_round_adaptive executes. Its time counts in
    the `route` phase of reconcile_route, its one caller in the package."""
    idxs = sorted(int(i) for i in idxs)
    if not megabatch_enabled() or len(idxs) < MEGABATCH_MIN_DOCS:
        return RoundPlan("per_doc", idxs)
    i_used, l_used = rset._mega_doc_sizes(idxs)
    dims_i, a, dims_le, _a_set, _a_del = rset.dims()
    buckets = plan_megabuckets(i_used, l_used, (dims_i, a, dims_le),
                               rset.cap_elems)
    est_mega = 0.0
    for b in buckets:
        i_b, le_b = b["dims"]
        wire = rows_count(i_b, a, le_b) * pad_to_lanes(len(b["docs"])) * 4
        est_mega += _device_cost(wire, 1)
    full_rows = rows_count(dims_i, a, dims_le)
    n = len(rset.doc_ids)
    alt_lanes = rset.n_pad if 2 * len(idxs) >= n \
        else pad_to_lanes(len(idxs))
    est_alt = _device_cost(full_rows * alt_lanes * 4, 1)
    if est_mega <= est_alt:
        return RoundPlan("megabatch", idxs, buckets, est_mega, est_alt)
    metrics.bump("engine_megabatch_fallbacks")
    return RoundPlan("per_doc", idxs, buckets, est_mega, est_alt)


def apply_round_adaptive(rset, plan: RoundPlan, interpret: bool = False):
    """Execute a megabatch-routed RoundPlan: per bucket, ONE fused
    reconcile over a gathered [rows(bucket dims), k_pad] sub-buffer of
    the host row mirror — the subset-layout property pack.mega_row_map
    documents makes the hashes bit-identical to the per-doc path. The
    per-doc hash mirror is refreshed in place (the offset tables make
    unpacking exact); returns the round's occupancy summary, or None
    when the plan routed per-doc (the lanes stay dirty: the caller
    reconciles them by their share of the fleet)."""
    if plan is None or plan.route != "megabatch" or not plan.buckets:
        return None
    from .pallas_kernels import reconcile_rows_hash

    dims_i, a, dims_le, a_set, a_del = rset.dims()
    mirror = rset._ensure_hash_mirror()
    idxs = plan.docs
    logical = padded = docs_cap = 0
    tenant_lanes: dict[str, float] = {}
    tenant_of = None
    try:
        from ..sync import tenantledger
        if tenantledger.enabled():
            tenant_of = tenantledger.tenant_of
    except Exception:
        pass
    for b in plan.buckets:
        docs = [idxs[p] for p in b["docs"].tolist()]
        k = len(docs)
        k_pad = pad_to_lanes(k)
        i_b, le_b = b["dims"]
        rmap = mega_row_map(dims_i, a, dims_le, i_b, le_b)
        # padding lanes must be valid doc columns (the _reconcile_lanes
        # rule): repeat the last doc, discard its extra hashes below
        sel = np.asarray(docs + [docs[-1]] * (k_pad - k), np.int64)
        with perfscope.phase("pack"):
            sub = rset.rows_host[np.ix_(rmap, sel)]
        rows_b = len(rmap)
        sub_dev = rset._to_dev(sub)
        with dispatchledger.call_scope(
                "rows_mega", backend="device", docs=k,
                axes={"docs": (k, k_pad), "rows": (rows_b, rows_b)}):
            h = metrics.dispatch_jit(
                "reconcile_rows_hash", reconcile_rows_hash,
                sub_dev, (i_b, a, le_b, a_set, a_del), interpret)
        with perfscope.phase("readback"):
            vals = rset._to_host(h)
        mirror[np.asarray(docs, np.int64)] = vals[:k]
        rset._doc_dirty.difference_update(docs)
        logical += rows_b * k
        padded += rows_b * k_pad
        docs_cap += k_pad
        if tenant_of is not None:
            lane_cost = rows_b * k_pad / k
            for d in docs:
                tid = tenant_of(rset.doc_ids[d])
                tenant_lanes[tid] = tenant_lanes.get(tid, 0.0) + lane_cost
    nb = len(plan.buckets)
    summary = {
        "buckets": nb,
        "docs": len(idxs),
        "dispatches": nb,
        "docs_cap": docs_cap,
        "logical": logical,
        "padded": padded,
        "docs_per_dispatch": round(len(idxs) / nb, 4),
        "fill_pct": round(100.0 * len(idxs) / docs_cap, 3) if docs_cap
        else None,
        "pad_waste_pct": round(100.0 * (1.0 - logical / padded), 3)
        if padded else None,
    }
    if tenant_lanes:
        summary["tenant_lanes"] = tenant_lanes
    metrics.bump("engine_megabatch_rounds")
    metrics.bump("engine_megabatch_docs", len(idxs))
    dispatchledger.note_megabatch(summary)
    return summary


def plan_spans(n_docs: int, s_pad: int, passes: int = 1) -> Plan:
    """Backend plan for a batched span-table merge of `n_docs` documents
    whose span axis padded to `s_pad` lanes (engine/span_kernels.py). The
    wire is the packed [D, F, S_pad] block; the host alternative is the
    numpy reference path."""
    wire_bytes = n_docs * len(SPAN_FIELDS) * s_pad * 4
    dev = _device_cost(wire_bytes, passes)
    host = _LINK["span_fixed_s"] + n_docs * s_pad * _LINK["span_op_s"]
    return Plan("device" if dev < host else "host", dev, host)


def merge_spans_adaptive(doc_spans: list, passes: int = 1):
    """Route a batched span-table merge through the cheaper backend.
    Returns (plan, result dict) — result arrays are numpy on the host
    path, device arrays on the device path (same schema)."""
    from .span_kernels import merge_spans, merge_spans_host

    spans = pack_spans(doc_spans)
    plan = plan_spans(spans.shape[0], spans.shape[2], passes)
    metrics.bump("engine_span_merges", backend=plan.backend)
    s_max = max((len(sp) for sp in doc_spans), default=0)
    with dispatchledger.call_scope(
            "spans", plan=plan, docs=len(doc_spans),
            axes={"docs": (spans.shape[0], spans.shape[0]),
                  "spans": (s_max, spans.shape[2])}):
        if plan.backend == "host":
            return plan, merge_spans_host(spans)
        return plan, merge_spans(spans)


def plan_moves(n_docs: int, n_pad: int, k_pad: int,
               passes: int = 1) -> Plan:
    """Backend plan for a batched move cycle-resolution of `n_docs`
    realms padded to `n_pad` node / `k_pad` candidate lanes
    (engine/move_kernels.py). The wire is the two packed lane blocks;
    the host alternative is the numpy fixpoint."""
    wire_bytes = n_docs * (len(MOVE_NODE_FIELDS) * n_pad
                           + len(MOVE_CAND_FIELDS) * k_pad) * 4
    dev = _device_cost(wire_bytes, passes)
    host = (_LINK["move_fixed_s"]
            + n_docs * (n_pad + k_pad) * _LINK["move_lane_s"])
    return Plan("device" if dev < host else "host", dev, host)


def resolve_moves_adaptive(packed: dict, passes: int = 1):
    """Route a batched move resolution through the cheaper backend.
    Returns (plan, result dict) — numpy arrays on the host path, device
    arrays on the device path (same schema)."""
    from .move_kernels import resolve_moves, resolve_moves_host

    nodes = packed["nodes"]
    plan = plan_moves(nodes.shape[0], nodes.shape[2],
                      packed["cands"].shape[2], passes)
    metrics.bump("engine_move_resolves", backend=plan.backend)
    # logical lane occupancy from the packed masks (row 0 is the node
    # mask, row 3 the per-node candidate counts)
    n_log = int(np.asarray(nodes)[:, 0, :].sum(axis=1).max(initial=0))
    k_log = int(np.asarray(nodes)[:, 3, :].sum(axis=1).max(initial=0))
    with dispatchledger.call_scope(
            "moves", plan=plan, docs=nodes.shape[0],
            axes={"docs": (nodes.shape[0], nodes.shape[0]),
                  "nodes": (n_log, nodes.shape[2]),
                  "cands": (k_log, packed["cands"].shape[2])}):
        if plan.backend == "host":
            return plan, resolve_moves_host(packed)
        return plan, resolve_moves(packed["nodes"], packed["cands"])


def _causal_order(changes):
    """Stable causal (re)ordering of a complete change list. Returns the
    input unchanged when it is already causally ordered (one O(n) clock
    pass), a stably reordered copy when a causal order exists, or None when
    none does (missing deps, duplicate or gapped seqs) — the interpretive
    path owns those semantics (causal queueing, seq-reuse errors).

    Why: bulk build requires application order (bulkload.py validates it),
    but get_missing_changes emits per-actor runs whose deps point across
    runs (op_set.js:299-306 does the same) — without this reorder every
    merged-doc log paid a failed bulk attempt and fell back (the r3 bench's
    config-3 routing tax). The reorder is a Kahn walk over per-actor
    chains with dep wait-heaps: O(n + deps·log) even on ping-pong-merged
    logs whose per-actor runs interleave change by change."""
    import heapq
    from collections import defaultdict, deque

    clock: dict[str, int] = {}
    for c in changes:
        if c.seq != clock.get(c.actor, 0) + 1 or any(
                clock.get(a, 0) < s for a, s in c.deps.items()):
            break
        clock[c.actor] = c.seq
    else:
        return changes

    chains: dict[str, list] = defaultdict(list)
    for c in changes:
        chains[c.actor].append(c)
    for a, chain in chains.items():
        chain.sort(key=lambda c: c.seq)
        if [c.seq for c in chain] != list(range(1, len(chain) + 1)):
            return None  # duplicate or gapped seqs: interpretive semantics

    clock = {}
    ptr = {a: 0 for a in chains}
    # waiting[a]: heap of (dep_seq, blocked_actor) — actors whose chain
    # head needs clock[a] >= dep_seq before it can advance
    waiting: dict[str, list] = defaultdict(list)
    ready = deque(chains)
    out: list = []
    while ready:
        a = ready.popleft()
        chain = chains[a]
        while ptr[a] < len(chain):
            c = chain[ptr[a]]
            unmet = next(((da, ds) for da, ds in c.deps.items()
                          if clock.get(da, 0) < ds), None)
            if unmet is not None:
                heapq.heappush(waiting[unmet[0]], (unmet[1], a))
                break
            out.append(c)
            clock[a] = c.seq
            ptr[a] += 1
            w = waiting.get(a)
            while w and w[0][0] <= clock[a]:
                ready.append(heapq.heappop(w)[1])
    if len(out) != len(changes):
        return None  # some dep is outside the log: no causal order exists
    return out


def apply_host(changes, actor_id: str = "engine"):
    """Host-path from-scratch apply of one document's complete change set:
    bulk vectorized build when the log is big enough and eligible, else
    interpretive replay. Returns the materialized document (same contract
    as the oracle path the bench compares against)."""
    from ..api import init
    from ..core.bulkload import try_bulk_build
    from ..frontend.materialize import apply_changes_to_doc, materialize_root
    from ..native.wire import changes_to_columns

    if len(changes) >= HOST_BULK_MIN_CHANGES:
        # try_bulk_build owns the fallback contract (GC pause, observable
        # core_bulk_fallbacks counter); materialize errors surface
        ordered = _causal_order(changes)
        if ordered is not None:
            opset = try_bulk_build(changes_to_columns(ordered))
            if opset is not None:
                metrics.bump("engine_bulk_built")
                return materialize_root(actor_id, opset)
    doc = init(actor_id)
    # no-diff apply: a from-scratch load has no diff consumer, so the
    # per-op edit records and O(sqrt n) sequence-index upkeep are skipped
    # and elem_ids rebuilds once per list (opset.add_changes docstring)
    return apply_changes_to_doc(doc, doc._doc.opset, list(changes),
                                incremental=False, emit_diffs=False)


def apply_batch_adaptive(doc_changes: list, passes: int = 1):
    """Route a from-scratch DocSet batch through the cheaper backend.

    Returns (plan, result): result is a list of materialized documents on
    the host path, or the per-doc state-hash array on the device path
    (the device's readable-state decode is on-demand, engine/batchdoc.py).
    """
    plan = plan_for(doc_changes, passes)
    with metrics.trace("engine_dispatch", backend=plan.backend), \
            dispatchledger.call_scope("apply", plan=plan,
                                      docs=len(doc_changes),
                                      axes=getattr(plan, "dims", None)):
        if plan.backend == "host":
            return plan, [apply_host(chs) for chs in doc_changes]
        from .batchdoc import apply_batch
        _encs, _batch, out = apply_batch(doc_changes)
        return plan, np.asarray(out["hash"])
