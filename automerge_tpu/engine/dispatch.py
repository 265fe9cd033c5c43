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
own host/device offload decisions.

The cost-model constants below were not measured on a directly attached
chip: they price a dispatch at 25 ms and a readback at 70 ms, which no
chip run of this round bears out. Re-pricing them from the chip, or
deleting the decisions one side always wins, is ROADMAP S3; calibrate()
overrides them meanwhile.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..utils import perfscope

# Link cost model (seconds). Not measured on the chip (ROADMAP S3).
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
    from .pack import pad_to_lanes, rows_count

    def _pad(n, minimum=8):
        p = minimum
        while p < n:
            p *= 2
        return p

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
    ops_pad = _pad(max_ops)
    ins_pad = _pad(max_ins)
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
# Megabatch round planning (r20): one fused multi-doc dispatch per flush
# round. pack.plan_megabuckets quantizes the round's ragged doc sizes onto
# a small shape ladder; this planner prices the fused bucketed dispatches
# against what the engine would otherwise do (full-buffer reconcile when a
# majority of the fleet is dirty, the narrow full-dims lane gather
# otherwise) and apply_round_adaptive executes the winning route.

_megabatch: bool | None = None
_megabatch_min: int | None = None


def megabatch_enabled() -> bool:
    """AMTPU_MEGABATCH != "0" (default on). One cached check — the
    disabled path costs a single comparison per round."""
    global _megabatch
    if _megabatch is None:
        _megabatch = os.environ.get("AMTPU_MEGABATCH", "1") != "0"
    return _megabatch


def megabatch_min_docs() -> int:
    """Routing threshold (AMTPU_MEGABATCH_MIN_DOCS, default 2): rounds
    dirtying fewer docs stay on the per-doc path — no batch of one can
    amortize bucket planning."""
    global _megabatch_min
    if _megabatch_min is None:
        try:
            _megabatch_min = max(
                int(os.environ.get("AMTPU_MEGABATCH_MIN_DOCS", "2")), 1)
        except ValueError:
            _megabatch_min = 2
    return _megabatch_min


def _reload_for_tests() -> None:
    global _megabatch, _megabatch_min
    _megabatch = None
    _megabatch_min = None


@dataclass
class RoundPlan:
    route: str                      # "megabatch" | "per_doc"
    docs: list = field(default_factory=list)    # doc indices, sorted
    buckets: list = field(default_factory=list)  # pack.plan_megabuckets
    est_mega_s: float = 0.0
    est_alt_s: float = 0.0


@perfscope.phased("route")
def plan_round(rset, idxs) -> RoundPlan:
    """Round-level routing for the dirty docs `idxs` of a resident set:
    bucket their exact used sizes (band scans — correct across
    compaction/rebuild) and compare the fused bucketed dispatches against
    the per-doc-path alternative. Returns a RoundPlan whose buckets are
    the offset tables apply_round_adaptive executes."""
    from ..utils import metrics
    from .pack import pad_to_lanes, plan_megabuckets, rows_count

    idxs = sorted(int(i) for i in idxs)
    if not megabatch_enabled() or len(idxs) < megabatch_min_docs():
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
    when the plan routed per-doc (caller falls through to the classic
    paths)."""
    if plan is None or plan.route != "megabatch" or not plan.buckets:
        return None
    import numpy as np

    from ..utils import metrics
    from . import dispatchledger
    from .pack import mega_row_map, pad_to_lanes
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
    from .pack import SPAN_FIELDS

    wire_bytes = n_docs * len(SPAN_FIELDS) * s_pad * 4
    dev = _device_cost(wire_bytes, passes)
    host = _LINK["span_fixed_s"] + n_docs * s_pad * _LINK["span_op_s"]
    return Plan("device" if dev < host else "host", dev, host)


def merge_spans_adaptive(doc_spans: list, passes: int = 1):
    """Route a batched span-table merge through the cheaper backend.
    Returns (plan, result dict) — result arrays are numpy on the host
    path, device arrays on the device path (same schema)."""
    from ..utils import metrics
    from .pack import pack_spans
    from .span_kernels import merge_spans, merge_spans_host

    from . import dispatchledger

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
    from .pack import MOVE_CAND_FIELDS, MOVE_NODE_FIELDS

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
    from ..utils import metrics
    from .move_kernels import resolve_moves, resolve_moves_host

    import numpy as _np

    from . import dispatchledger

    nodes = packed["nodes"]
    plan = plan_moves(nodes.shape[0], nodes.shape[2],
                      packed["cands"].shape[2], passes)
    metrics.bump("engine_move_resolves", backend=plan.backend)
    # logical lane occupancy from the packed masks (row 0 is the node
    # mask, row 3 the per-node candidate counts)
    n_log = int(_np.asarray(nodes)[:, 0, :].sum(axis=1).max(initial=0))
    k_log = int(_np.asarray(nodes)[:, 3, :].sum(axis=1).max(initial=0))
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
                from ..utils import metrics
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
    import numpy as np

    from ..utils import metrics

    from . import dispatchledger

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
