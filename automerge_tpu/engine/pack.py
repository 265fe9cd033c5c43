"""Packed single-buffer wire format for batch transfer.

Every host->device transfer has a fixed cost, so shipping a batch as 14
separate arrays pays it 14 times (how much that is on the chip is not
measured). This module flattens an entire stacked batch into ONE int32
buffer; the
device unpacks it with static slices/reshapes inside the jitted program
(free — XLA folds them into the consumers).

This is also the natural DCN wire format for multi-host DocSet sync: one
contiguous block per batch, int32 throughout, shapes carried in a tiny
static header.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import perfscope

# field order is the wire contract
FIELDS = ("op_mask", "action", "fid", "actor", "seq", "change_idx", "value",
          "fid_hash", "value_hash", "clock", "ins_mask", "ins_elem",
          "ins_actor", "ins_parent", "ins_fid", "ins_pos", "list_obj",
          "list_obj_hash", "actor_hash")

# TPU lane width: the docs axis of every docs-minor layout pads to a
# multiple of this. THE canonical constant — every layer that pads the
# docs axis must go through pad_to_lanes (the graftlint jit-shape-drift
# rule flags open-coded `((n + 127) // 128) * 128` elsewhere; two layers
# disagreeing about padding is a shape-mismatch crash at dispatch time).
LANE = 128


def pad_to_lanes(n: int) -> int:
    """Round a doc count up to the TPU lane width (docs-minor layouts)."""
    return ((n + LANE - 1) // LANE) * LANE


@perfscope.phased("pack")
def pack_batch(batch: dict) -> tuple[np.ndarray, tuple]:
    """Flatten a stacked batch into (flat int32 buffer, static meta).

    meta is hashable (usable as a static jit argument): a tuple of
    (name, offset, shape, is_bool) entries.
    """
    parts = []
    meta = []
    offset = 0
    for name in FIELDS:
        arr = np.asarray(batch[name])
        flat = arr.astype(np.int32).ravel()
        meta.append((name, offset, arr.shape, arr.dtype == np.bool_))
        parts.append(flat)
        offset += flat.size
    return np.concatenate(parts), tuple(meta)


def unpack_batch(flat, meta: tuple) -> dict:
    """Device-side unpack (inside jit): static slices + reshapes."""
    out = {}
    for name, offset, shape, is_bool in meta:
        size = int(np.prod(shape))
        arr = jax.lax.slice(flat, (offset,), (offset + size,)).reshape(shape)
        if is_bool:
            arr = arr.astype(bool)
        out[name] = arr
    return out


@partial(jax.jit, static_argnames=("meta", "max_fids", "host_order"))
def apply_packed_hash(flat, meta: tuple, max_fids: int,
                      host_order: bool = True):
    """One reconcile pass over a packed batch, returning ONLY the per-doc
    state hashes (the minimal readback for convergence checking)."""
    from .kernels import apply_doc
    batch = unpack_batch(flat, meta)
    return apply_doc.__wrapped__(batch, max_fids, host_order)["hash"]


@partial(jax.jit, static_argnames=("meta", "max_fids", "host_order"))
def apply_packed(flat, meta: tuple, max_fids: int, host_order: bool = True):
    """Full reconcile over a packed batch (all per-doc state arrays)."""
    from .kernels import apply_doc
    batch = unpack_batch(flat, meta)
    return apply_doc.__wrapped__(batch, max_fids, host_order)


# ---------------------------------------------------------------------------
# Docs-minor row wire format (the pallas megakernel's native layout)

# Row-buffer column groups, in wire order. `ins_elem/ins_actor/ins_parent`
# are deliberately absent: the hash path uses host-linearized positions
# (ins_pos), so the RGA tree columns never need to cross the wire. `clock_op`
# is each op's own change-clock row (actor-major), so the kernel never
# indexes by change id; `elem_list` is the owning-list row per element slot
# (a static iota pattern).
ROW_FIELDS = ("op_mask", "action", "fid", "actor", "seq", "change_idx",
              "fid_hash", "value_hash", "clock_op", "ins_mask", "ins_fid",
              "ins_pos", "elem_objhash", "elem_list", "actor_hash")

# VMEM bounds for the blocked megakernel. Neither the change count C nor the
# field count F appears: clock_op replaces per-change clocks and fid equality
# is joined directly (VERDICT r1 #5 — the old unrolled kernel capped I/F/L*E
# at 64 and C*A at 512). The working-set model below is in units of
# [1, 128]-lane int32 rows (512B each): the input block (rows_count), the
# ~three live 8-row join intermediates (24 * max(I, LE)), and the five
# scratch accumulators (3I + 2LE). The budget sits just under the largest
# configuration measured to compile on the v5e this repo benches on
# (I=512, A=8, LE=128 -> 22912 rows compiled; I=512, A=8, LE=512 -> 25600
# rows did not).
ROWS_MAX_OPS = 1024
ROWS_MAX_ELEMS = 1024
ROWS_VMEM_BUDGET = 22528   # rows-equivalents: ~11MB of VMEM working set


def rows_count(i: int, a: int, le: int) -> int:
    """Input-buffer row count of the docs-minor layout (the wire size is
    rows_count * d_pad * 4 bytes)."""
    return 8 * i + a * i + 5 * le + a


def row_bases(i: int, a: int, le: int) -> dict:
    """Row offsets of each ROW_FIELDS group in the docs-minor buffer — the
    ONE definition of the layout, shared by the kernel builders
    (pallas_kernels) and the resident rows mirror (resident_rows._bases).
    The trailing "ah" band is the rank -> actor CONTENT hash table the
    state hash mixes (kernels.state_hash: rank-basis independence)."""
    co = 8 * i
    return {
        "om": 0, "ac": i, "fid": 2 * i, "act": 3 * i, "seq": 4 * i,
        "chg": 5 * i, "fh": 6 * i, "vh": 7 * i, "co": co,
        "im": co + a * i, "if": co + a * i + le, "ip": co + a * i + 2 * le,
        "io": co + a * i + 3 * le, "il": co + a * i + 4 * le,
        "ah": co + a * i + 5 * le,
        "rows": co + a * i + 5 * le + a,
    }


def rows_dims_eligible(i: int, a: int, le: int) -> bool:
    """Whether per-doc dims (ops, actors, list-element slots) fit the
    megakernel's VMEM working set. I and LE must be multiples of the kernel
    block height (8) — encode.py's _pad_to guarantees this for in-repo
    producers; external callers must pad."""
    working = rows_count(i, a, le) + 24 * max(i, le) + 3 * i + 2 * le
    return (i % 8 == 0 and le % 8 == 0
            and i <= ROWS_MAX_OPS and le <= ROWS_MAX_ELEMS
            and working <= ROWS_VMEM_BUDGET)


def rows_kernel(i: int, a: int, le: int) -> str | None:
    """The reconcile kernel that takes per-doc dims (ops, actors,
    list-element slots): "standard" where rows_dims_eligible holds, else
    "xl" where the doubly blocked variant that `reconcile_rows_hash` falls
    back to does (pallas_kernels.rows_dims_eligible_xl), else None."""
    if rows_dims_eligible(i, a, le):
        return "standard"
    from .pallas_kernels import rows_dims_eligible_xl
    return "xl" if rows_dims_eligible_xl(i, a, le) else None


def rows_dims_fit(i: int, a: int, le: int) -> bool:
    """The resident engines' admission budget over per-doc dims: a kernel
    takes them (rows_kernel), and the XL variant only where the standard
    kernel would take the op axis alone. So the XL variant stretches the
    element axis only; history past the standard kernel is compaction's to
    reclaim (engine/compaction.py), since a longer op band costs every
    lane."""
    kernel = rows_kernel(i, a, le)
    return kernel == "standard" or (
        kernel == "xl" and le <= ROWS_MAX_ELEMS
        and rows_dims_eligible(i, a, 0))


def rows_eligible(batch: dict, max_fids: int) -> bool:
    d, i = batch["op_mask"].shape
    a = batch["clock"].shape[2]
    l, e = batch["ins_mask"].shape[1:]
    return rows_kernel(i, a, l * e) is not None


@perfscope.phased("pack")
def pack_rows(batch: dict, max_fids: int) -> tuple[np.ndarray, tuple, int]:
    """Repack a stacked batch (docs-major dict) into the docs-minor
    [ROWS, D_pad] int32 row buffer + static dims for reconcile_rows_hash.

    Returns (rows, dims, n_docs). D_pad rounds the doc count up to a
    multiple of 128 (the TPU lane width); padded docs hash to garbage and
    are sliced off after readback.
    """
    from .encode import A_DEL, A_SET

    d, i = batch["op_mask"].shape
    c, a = batch["clock"].shape[1:]
    l, e = batch["ins_mask"].shape[1:]
    d_pad = pad_to_lanes(d)

    def rowify(arr, fill=0):
        """[d, ...] -> [prod(...), d_pad] int32, docs minor."""
        arr = np.asarray(arr).astype(np.int32)
        flat = arr.reshape(d, -1).T
        if d_pad > d:
            flat = np.pad(flat, ((0, 0), (0, d_pad - d)),
                          constant_values=fill)
        return flat

    # per-op clock rows: clock_op[d, i, a] = clock[d, change_idx[d, i], a],
    # then actor-major [d, a, i] so the kernel's per-actor bands are
    # contiguous row ranges.
    chg = np.clip(np.asarray(batch["change_idx"]), 0, c - 1)
    clock_op = np.take_along_axis(
        np.asarray(batch["clock"]),
        chg[:, :, None].astype(np.int64), axis=1)          # [d, i, a]
    clock_op_am = np.moveaxis(clock_op, 2, 1)              # [d, a, i]

    elem_objhash = np.broadcast_to(
        np.asarray(batch["list_obj_hash"])[:, :, None], (d, l, e))
    elem_list = np.broadcast_to(
        np.arange(l, dtype=np.int32)[None, :, None], (d, l, e))
    parts = [
        rowify(batch["op_mask"]), rowify(batch["action"], -1),
        rowify(batch["fid"], -1), rowify(batch["actor"]),
        rowify(batch["seq"]), rowify(batch["change_idx"]),
        rowify(batch["fid_hash"]), rowify(batch["value_hash"]),
        rowify(clock_op_am), rowify(batch["ins_mask"]),
        rowify(batch["ins_fid"], -1), rowify(batch["ins_pos"]),
        rowify(elem_objhash, -1), rowify(elem_list, -1),
        rowify(batch["actor_hash"]),
    ]
    rows = np.concatenate(parts, axis=0)
    dims = (i, a, l * e, int(A_SET), int(A_DEL))
    return rows, dims, d


def apply_rows_hash(rows, dims: tuple, n_docs: int, interpret: bool = False):
    """Per-doc state hashes from a row buffer via the pallas megakernel
    (TPU) or its interpreter (tests/CPU). Returns uint32 [n_docs]."""
    from .pallas_kernels import reconcile_rows_hash
    return reconcile_rows_hash(rows, dims, interpret)[:n_docs]


# ---------------------------------------------------------------------------
# Megabatch plane (r20): multi-doc fused dispatch over the docs-minor rows
#
# Independent documents already share lanes in the docs-minor buffer above;
# what they do NOT share is SHAPE — one 16-op doc in a fleet grown to
# I=1024 pays the whole 1024-row band. The megabatch plane fixes that by
# observing that a smaller-dims (I', A, L'*E) layout is a pure ROW-INDEX
# SUBSET of the full (I, A, L*E) layout for the same lanes, provided the
# elem-slot stride E is preserved (whole lists only):
#
#   op bands        rows g + [0, I')           per op group g
#   clock band      rows co + a*I + [0, I')    per actor a (strided)
#   elem bands      rows g + [0, L'*E)         per elem group g
#   ah band         all A rows
#
# Every band is lane-independent in the kernel (pallas_kernels: one output
# per column), op/elem rows join only within their own band ranges, and
# unused rows (op_mask=0 / ins_mask=0) contribute nothing to the hash — so
# hashing the subset buffer at dims (I', A, L'*E) is BIT-IDENTICAL to
# hashing the full buffer, for any I' >= ops_used and L' >= lists_used of
# every selected lane. Ragged per-doc sizes are bucketed onto a power-of-
# two ladder (the way pack_moves rank-compresses priorities) so a round
# compiles to at most MEGA_MAX_BUCKETS kernel shapes; each bucket carries
# its doc-index table, so unpacking the per-doc hashes is exact.

#: distinct padded shapes per megabatched round — bounds both the compile
#: cache and the per-round dispatch count (the amplification ceiling)
MEGA_MAX_BUCKETS = 4
#: smallest quantized op/list band (the kernel block height)
MEGA_MIN_DIM = 8


def mega_quantize(n: int, cap: int) -> int:
    """Power-of-two ladder from MEGA_MIN_DIM up to (and clamped at) cap:
    the bucket-shape rank compression. cap itself need not be a power of
    two — the top rung is the fleet dimension."""
    q = MEGA_MIN_DIM
    while q < n:
        q *= 2
    return min(q, cap)


def mega_bucket_dims(i_used: int, l_used: int, caps: tuple,
                     e: int) -> tuple:
    """Quantized (i_b, le_b) bucket dims for one doc's used sizes under
    fleet caps (I, A, LE). Elem slots subset at LIST granularity only
    (le_b = l_b * e keeps the slot stride), and both dims must stay
    multiples of the kernel block height; when alignment cannot be met
    the dimension falls back to the full fleet value."""
    i_cap, a, le_cap = caps
    i_b = mega_quantize(max(int(i_used), 1), i_cap)
    if i_b % 8:
        i_b = i_cap
    if le_cap == 0 or e == 0:
        return i_b, 0
    l_cap = le_cap // e
    l_b = mega_quantize(max(int(l_used), 1), l_cap) if l_used else 0
    while l_b < l_cap and (l_b * e) % 8:
        l_b *= 2
    le_b = min(l_b * e, le_cap)
    if le_b % 8:
        le_b = le_cap
    return i_b, le_b


def mega_row_map(i: int, a: int, le: int, i_b: int,
                 le_b: int) -> np.ndarray:
    """Row indices into the full (i, a, le) docs-minor buffer that
    gather a valid (i_b, a, le_b) buffer for the SAME doc lanes — the
    subset property the module comment proves. Length is
    rows_count(i_b, a, le_b); row_bases is the one layout definition on
    both sides."""
    src = row_bases(i, a, le)
    ops = np.arange(i_b, dtype=np.int64)
    elems = np.arange(le_b, dtype=np.int64)
    parts = [src[g] + ops
             for g in ("om", "ac", "fid", "act", "seq", "chg", "fh", "vh")]
    parts.extend(src["co"] + aa * i + ops for aa in range(a))
    parts.extend(src[g] + elems for g in ("im", "if", "ip", "io", "il"))
    parts.append(src["ah"] + np.arange(a, dtype=np.int64))
    out = np.concatenate(parts)
    assert len(out) == rows_count(i_b, a, le_b)
    return out


def plan_megabuckets(i_used, l_used, caps: tuple, e: int) -> list[dict]:
    """Bucket a round's docs by quantized shape: positions i group under
    (i_b, le_b) = mega_bucket_dims(i_used[i], l_used[i]). More than
    MEGA_MAX_BUCKETS distinct shapes merge smallest-volume-first into
    their elementwise-max superset (any doc hashes identically at any
    dims >= its used sizes, so merging only adds padding, never error).

    Returns [{"dims": (i_b, le_b), "docs": np.ndarray positions}],
    largest bucket first — the offset tables that make unpacking exact.
    """
    i_used = np.asarray(i_used, np.int64)
    l_used = np.asarray(l_used, np.int64)
    groups: dict[tuple, list] = {}
    for pos in range(len(i_used)):
        key = mega_bucket_dims(int(i_used[pos]), int(l_used[pos]), caps, e)
        groups.setdefault(key, []).append(pos)
    a_rows = caps[1]
    while len(groups) > MEGA_MAX_BUCKETS:
        # merge the smallest padded volume into its cheapest superset
        small = min(groups, key=lambda k: (rows_count(k[0], a_rows, k[1])
                                           * len(groups[k])))
        members = groups.pop(small)
        best = min(groups,
                   key=lambda k: rows_count(max(k[0], small[0]), a_rows,
                                            max(k[1], small[1])))
        merged = (max(best[0], small[0]), max(best[1], small[1]))
        members.extend(groups.pop(best))
        groups.setdefault(merged, []).extend(members)
    out = [{"dims": k, "docs": np.asarray(sorted(v), np.int64)}
           for k, v in groups.items()]
    out.sort(key=lambda b: -len(b["docs"]))
    return out


# ---------------------------------------------------------------------------
# Span-table lane layout (the batched text-merge plane's wire shape)
#
# A span table is the run-length-encoded form of a text document's visible
# order: one row per maximal run of consecutively-numbered same-origin
# elements (core/textspans.spans_of_elems), extended for merging with the
# anchor/priority columns the merge-order kernel sorts by. Like the row
# buffer above, the layout is lane-native: per document, one int32
# [len(SPAN_FIELDS), S_pad] block with the SPAN axis minor (padded to the
# TPU lane width), so a fleet of divergent documents merges as one
# [D, F, S_pad] dispatch with zero relayouts.
#
# Merge-order encoding (engine/span_kernels.py sorts by it):
#   slot       2*i for the i-th span of the base (common-history) table;
#              2*g+1 for a concurrent span anchored in the gap after base
#              span g (-1 for the head gap), so concurrent spans interleave
#              between the base spans they were typed between;
#   prio_elem/prio_actor  RGA sibling priority of the span's head element —
#              concurrent spans in one gap order by (elem, actor)
#              DESCENDING, the reference's sibling rule (op_set.js:343-362);
#   block_seq  ascending tiebreak keeping a flattened subtree block (one
#              side's nested spans in one gap) contiguous and in its
#              side-local document order.

SPAN_FIELDS = ("span_mask", "origin_hash", "start_id", "vis_len", "slot",
               "prio_elem", "prio_actor", "block_seq")


@perfscope.phased("pack")
def pack_spans(doc_spans: list) -> np.ndarray:
    """Pack per-document span tables into [D, len(SPAN_FIELDS), S_pad]
    int32 lanes. Each span is an (origin_hash, start_id, vis_len, slot,
    prio_elem, prio_actor, block_seq) tuple; the mask row is synthesized.
    The span axis pads to the TPU lane width (pad_to_lanes) — padded slots
    mask out and sort to the end inside the kernel."""
    from ..utils import metrics

    d = len(doc_spans)
    s_max = max((len(sp) for sp in doc_spans), default=0)
    s_pad = pad_to_lanes(max(s_max, 1))
    out = np.zeros((d, len(SPAN_FIELDS), s_pad), np.int32)
    for i, spans in enumerate(doc_spans):
        if not spans:
            continue
        arr = np.asarray(spans, np.int64).T  # [7, s]
        if arr.shape[0] != len(SPAN_FIELDS) - 1:
            raise ValueError(
                f"span tuples must have {len(SPAN_FIELDS) - 1} columns "
                f"({SPAN_FIELDS[1:]}), got {arr.shape[0]}")
        out[i, 0, :arr.shape[1]] = 1
        out[i, 1:, :arr.shape[1]] = arr.astype(np.int32)
    metrics.bump("engine_span_tables_packed", d)
    return out


# ---------------------------------------------------------------------------
# Compact wire: dtype-narrowed row buffers
#
# The row buffer is all-int32 on device (the megakernel's native layout),
# but most of its columns are tiny integers — masks, action codes, field
# ids, actor ranks, clock entries — while only the three content-hash
# groups need 32 bits. On a link where the host->device hop charges both
# per-call and per-byte (INTERNALS.md §4), shipping the rows at their
# NARROWEST safe width and widening on device (one fused cast+concat
# inside the same dispatch) cuts the wire ~2.5x for map-heavy batches and
# lets a whole multi-pass timed region ship as three transfer calls.
# pack_rows_compact chooses int8/int16/int32 PER FIELD from the observed
# value range, so the format stays exact for any batch.

def _narrow_dtype(part: np.ndarray):
    lo, hi = (int(part.min()), int(part.max())) if part.size else (0, 0)
    if -128 <= lo and hi <= 127:
        return 0, np.int8
    if -32768 <= lo and hi <= 32767:
        return 1, np.int16
    return 2, np.int32


_DTYPES = (np.int8, np.int16, np.int32)
# ROW_FIELDS positions of the content-hash groups: never narrowable.
# fields whose width is declared from a capacity bound with NO data
# inspection (classify_row_groups keys its cap_hi dict from this set) —
# the only ones where a narrow astype could silently wrap, so the only
# ones pack_rows_compact range-checks
_CAP_FIELDS = frozenset((
    "op_mask", "action", "fid", "actor", "ins_mask", "ins_fid", "ins_pos"))
_CAP_GROUPS = frozenset(ROW_FIELDS.index(f) for f in _CAP_FIELDS)
_HASH_GROUPS = frozenset((ROW_FIELDS.index("actor_hash"),
                          ROW_FIELDS.index("fid_hash"),
                          ROW_FIELDS.index("value_hash"),
                          ROW_FIELDS.index("elem_objhash")))


def _width_of_bound(lo: int, hi: int) -> int:
    if -128 <= lo and hi <= 127:
        return 0
    if -32768 <= lo and hi <= 32767:
        return 1
    return 2


def classify_row_groups(rows, dims: tuple, max_fids: int) -> tuple:
    """Batch-stable per-group dtype classes (ADVICE r3, pack.py:318): the
    classification is part of the jit static key, so it must not flap
    between batches of a stream. Three policies by group:

    - capacity-derived where the layout itself bounds the values (masks
      0/1, the action enum, fid < max_fids, actor rank < A, ins_pos < LE):
      no data inspection at all — identical for every batch of the same
      declared shape;
    - always-int32 for the content-hash groups (hashes span the word);
    - observed-max quantized with 2x headroom for the genuinely data-
      dependent counters (seq, change_idx, clock_op, elem_list): the class
      only changes when a counter actually crosses HALF a dtype boundary,
      so a streaming deployment retraces O(log) times over its lifetime
      instead of whenever a value grazes a boundary."""
    i, a, le = dims[0], dims[1], dims[2]
    cap_bound = {
        "op_mask": 1,
        "action": 32,       # enum, ~10 actions
        "fid": max(max_fids, 1),
        "actor": max(a, 1),
        "ins_mask": 1,
        "ins_fid": max(max_fids, 1),
        "ins_pos": max(le, 1),
    }
    assert set(cap_bound) == _CAP_FIELDS   # checker and classifier agree
    cap_hi = {ROW_FIELDS.index(f): v for f, v in cap_bound.items()}
    group_rows = (i, i, i, i, i, i, i, i, a * i,
                  le, le, le, le, le, a)
    widths = []
    off = 0
    for g, r in enumerate(group_rows):
        part = rows[off:off + r]
        off += r
        if g in _HASH_GROUPS:
            widths.append(2)
        elif g in cap_hi:
            widths.append(_width_of_bound(-1, cap_hi[g]))
        else:
            lo, hi = ((int(part.min()), int(part.max())) if part.size
                      else (0, 0))
            widths.append(_width_of_bound(min(lo, -1), max(2 * hi, 1)))
    return tuple(widths)


def pack_rows_compact(batch: dict, max_fids: int):
    """Docs-minor row wire with per-field narrow dtypes.

    Returns ((b8, b16, b32), meta, dims, n_docs): three [rows_dt, D_pad]
    buffers (possibly 0-row) holding the row groups of their width class
    in kernel order, and meta = ((dtype_idx, n_rows), ...) per ROW_FIELDS
    group, enough for widen_rows to rebuild the exact int32 layout."""
    rows, dims, d = pack_rows(batch, max_fids)

    # split back into the ROW_FIELDS groups; widths come from the
    # batch-stable policy (classify_row_groups) so the static jit key
    # does not flap between batches of a stream
    i, a, le = dims[0], dims[1], dims[2]
    group_rows = (i, i, i, i, i, i, i, i, a * i,
                  le, le, le, le, le, a)
    widths = classify_row_groups(rows, dims, max_fids)
    parts8, parts16, parts32, meta = [], [], [], []
    off = 0
    for g, (r, idx) in enumerate(zip(group_rows, widths)):
        part = rows[off:off + r]
        off += r
        if idx < 2 and part.size and g in _CAP_GROUPS:
            # a narrow astype silently wraps out-of-range values into
            # corrupt (but hashable) rows — fail loudly if a declared
            # capacity bound (ADVICE r4, pack.py:276) is ever violated.
            # Observed-max groups cannot wrap (their width came from this
            # same array with 2x headroom), so only capacity-derived
            # groups are scanned.
            info = np.iinfo(_DTYPES[idx])
            lo, hi = int(part.min()), int(part.max())
            if lo < info.min or hi > info.max:
                raise ValueError(
                    f"row group {g} [{lo}, {hi}] exceeds its declared "
                    f"{_DTYPES[idx].__name__} capacity — layout invariant "
                    f"violated (classify_row_groups)")
        (parts8, parts16, parts32)[idx].append(part.astype(_DTYPES[idx]))
        meta.append((idx, r))
    d_pad = rows.shape[1]

    def cat(parts, dt):
        if not parts:
            return np.zeros((0, d_pad), dt)
        return np.concatenate(parts, axis=0)

    return ((cat(parts8, np.int8), cat(parts16, np.int16),
             cat(parts32, np.int32)), tuple(meta), dims, d)


def widen_rows(b8, b16, b32, meta: tuple):
    """Device-side (inside jit): rebuild the [ROWS, D_pad] int32 row buffer
    from the narrow wire. One fused cast+concat — XLA folds it into the
    megakernel's input copy; no extra dispatch."""
    bufs = (b8, b16, b32)
    offs = [0, 0, 0]
    parts = []
    for idx, r in meta:
        src = bufs[idx]
        parts.append(jax.lax.slice(
            src, (offs[idx], 0),
            (offs[idx] + r, src.shape[1])).astype(jnp.int32))
        offs[idx] += r
    return jnp.concatenate(parts, axis=0)


@partial(jax.jit, static_argnames=("meta", "dims", "interpret"))
def apply_rows_hash_compact(b8, b16, b32, meta: tuple, dims: tuple,
                            interpret: bool = False):
    """reconcile_rows_hash over the compact wire (widen + kernel in ONE
    dispatch). Returns uint32 [D_pad] hashes."""
    from .pallas_kernels import reconcile_rows_hash
    rows = widen_rows(b8, b16, b32, meta)
    return reconcile_rows_hash.__wrapped__(rows, dims, interpret)


@perfscope.phased("pack")
def pack_rows_bytes(batch: dict, max_fids: int):
    """The compact wire as ONE contiguous uint8 buffer (the three dtype
    groups back to back, row-major). A multi-pass timed region can then
    stack passes on a leading axis and cross the link in a single transfer
    call. Returns (wire_u8[n_bytes], bmeta, dims, n_docs); bmeta =
    (meta, (r8, r16, r32), d_pad)."""
    (b8, b16, b32), meta, dims, n = pack_rows_compact(batch, max_fids)
    wire = np.concatenate(
        [np.ascontiguousarray(b).view(np.uint8).ravel()
         for b in (b8, b16, b32)])
    bmeta = (meta, (b8.shape[0], b16.shape[0], b32.shape[0]), b8.shape[1])
    return wire, bmeta, dims, n


def widen_bytes(wire_u8, bmeta: tuple):
    """Device-side (inside jit): [n_bytes] uint8 -> [ROWS, D_pad] int32.
    Byte-pair/quad reassembly uses bitcast_convert_type on little-endian
    lanes (XLA's defined in-memory layout on CPU and TPU)."""
    meta, (r8, r16, r32), d_pad = bmeta
    o8, o16 = r8 * d_pad, r8 * d_pad + r16 * d_pad * 2
    end = o16 + r32 * d_pad * 4
    b8 = jax.lax.bitcast_convert_type(
        jax.lax.slice(wire_u8, (0,), (o8,)).reshape(r8, d_pad),
        jnp.int8) if r8 else jnp.zeros((0, d_pad), jnp.int8)
    b16 = jax.lax.bitcast_convert_type(
        jax.lax.slice(wire_u8, (o8,), (o16,)).reshape(r16, d_pad, 2),
        jnp.int16) if r16 else jnp.zeros((0, d_pad), jnp.int16)
    b32 = jax.lax.bitcast_convert_type(
        jax.lax.slice(wire_u8, (o16,), (end,)).reshape(r32, d_pad, 4),
        jnp.int32) if r32 else jnp.zeros((0, d_pad), jnp.int32)
    return widen_rows(b8, b16, b32, meta)


@partial(jax.jit, static_argnames=("bmeta", "dims", "interpret"))
def apply_rows_hash_bytes(wire_u8, bmeta: tuple, dims: tuple,
                          interpret: bool = False):
    """reconcile_rows_hash over the single-buffer byte wire."""
    from .pallas_kernels import reconcile_rows_hash
    rows = widen_bytes(wire_u8, bmeta)
    return reconcile_rows_hash.__wrapped__(rows, dims, interpret)


# ---------------------------------------------------------------------------
# Field-sharding wide documents across virtual doc columns
#
# Survivor analysis only ever joins ops that share a field id, and the state
# hash is a commutative uint32 SUM over surviving assigns (kernels.state_hash)
# — so a wide document can be partitioned BY FIELD into several virtual
# documents whose hashes add back to the real document's hash exactly. This
# turns per-doc op count from a VMEM bound into a docs-axis parallelism
# bound: a 2048-op map document becomes four 512-op lane columns. List
# objects are atomic (their elements' rank join spans the list), so every
# list field group rides virtual doc 0 with the doc's insertion tables;
# make/ins op rows carry no kernel state (amask needs action >= set, and
# insertion data travels in the ins tables) and are dropped outright.

def select_field_sharding(batch: dict, max_fids: int):
    """The op-axis target ladder for wide documents: try splitting into
    field-disjoint virtual docs at each target (largest first, so the
    fewest virtual docs that fit the VMEM envelope win) and return
    (sharded_batch, owner, target_ops) for the first eligible split, or
    (None, None, None) when the ineligibility is elems/actors-driven and
    op-axis sharding cannot help. ONE ladder shared by bench.run_engine's
    device path and the interpret-mode bench-shape tests, so the tested
    split is always the shipped split."""
    a0 = batch["clock"].shape[2]
    le0 = batch["ins_mask"].shape[1] * batch["ins_mask"].shape[2]
    for target in (512, 256, 128):
        if not rows_dims_eligible(target, a0, le0):
            continue
        cand, owner = shard_batch_by_fields(batch, max_fids, target)
        if rows_eligible(cand, max_fids):
            return cand, owner, target
    return None, None, None


def shard_batch_by_fields(batch: dict, max_fids: int, target_ops: int = 512):
    """Split docs with more than `target_ops` assigns into field-disjoint
    virtual docs of at most `target_ops` assigns each.

    Returns (sharded_batch, owner): owner[v] = real doc index of virtual doc
    v; real_hash[d] = uint32 sum of virtual hashes with owner == d."""
    from .encode import A_SET

    d, i = batch["op_mask"].shape
    om = np.asarray(batch["op_mask"])
    action = np.asarray(batch["action"])
    fid = np.asarray(batch["fid"])
    ins_mask = np.asarray(batch["ins_mask"])
    ins_fid = np.asarray(batch["ins_fid"])

    virtuals: list[tuple[int, np.ndarray, bool]] = []  # (owner, op_idx, ins)
    max_bin = 1
    for dd in range(d):
        assigns = np.nonzero(om[dd] & (action[dd] >= A_SET))[0]
        if len(assigns) <= target_ops:
            virtuals.append((dd, assigns, True))
            max_bin = max(max_bin, len(assigns))
            continue
        list_fids = set(ins_fid[dd][ins_mask[dd]].tolist())
        list_fids.discard(-1)
        f_of = fid[dd][assigns]
        is_list_op = np.isin(f_of, list(list_fids)) if list_fids \
            else np.zeros(len(assigns), bool)
        bins: list[list[np.ndarray]] = [[assigns[is_list_op]]]
        sizes = [int(is_list_op.sum())]
        # group map assigns by fid, largest groups first (greedy best-fit)
        map_ops = assigns[~is_list_op]
        if len(map_ops):
            mf = fid[dd][map_ops]
            order = np.argsort(mf, kind="stable")
            srt = map_ops[order]
            fs = mf[order]
            bounds = np.nonzero(np.r_[True, fs[1:] != fs[:-1]])[0]
            groups = [srt[lo:hi] for lo, hi in
                      zip(bounds, np.r_[bounds[1:], len(srt)])]
            groups.sort(key=len, reverse=True)
            for g in groups:
                placed = False
                for b in range(len(bins)):
                    if sizes[b] + len(g) <= target_ops:
                        bins[b].append(g)
                        sizes[b] += len(g)
                        placed = True
                        break
                if not placed:
                    bins.append([g])
                    sizes.append(len(g))
        for b, parts in enumerate(bins):
            idx = np.concatenate(parts) if parts else np.zeros(0, np.int64)
            virtuals.append((dd, idx, b == 0))
            max_bin = max(max_bin, len(idx))

    i_t = 8
    while i_t < max_bin:
        i_t *= 2
    owner = np.fromiter((v[0] for v in virtuals), np.int64, len(virtuals))
    V = len(virtuals)

    out = {}
    fills = {"op_mask": False, "action": -1, "fid": -1, "value": -1}
    for name in ("op_mask", "action", "fid", "actor", "seq", "change_idx",
                 "value", "fid_hash", "value_hash"):
        src = np.asarray(batch[name])
        fill = fills.get(name, 0)
        arr = np.full((V, i_t), fill, dtype=src.dtype)
        for v, (dd, idx, _ins) in enumerate(virtuals):
            arr[v, :len(idx)] = src[dd, idx]
        out[name] = arr
    clock = np.asarray(batch["clock"])
    out["clock"] = clock[owner]
    out["actor_hash"] = np.asarray(batch["actor_hash"])[owner]
    for name in ("ins_mask", "ins_elem", "ins_actor", "ins_parent",
                 "ins_fid", "ins_pos", "list_obj", "list_obj_hash"):
        src = np.asarray(batch[name])
        fill = {"ins_mask": False, "ins_elem": 0, "ins_actor": 0}.get(
            name, -1)
        arr = np.full((V,) + src.shape[1:], fill, dtype=src.dtype)
        for v, (dd, _idx, takes_ins) in enumerate(virtuals):
            if takes_ins:
                arr[v] = src[dd]
        out[name] = arr
    return out, owner


def recombine_hashes(virtual_hashes: np.ndarray, owner: np.ndarray,
                     n_docs: int) -> np.ndarray:
    """real_hash[d] = uint32 wraparound sum of its virtual docs' hashes."""
    out = np.zeros(n_docs, np.uint32)
    np.add.at(out, owner, np.asarray(virtual_hashes)[:len(owner)]
              .astype(np.uint32))
    return out


# ---------------------------------------------------------------------------
# Move-resolution tables (ISSUE 15): the batched cycle-resolution working
# set. One realm (the map-object forest or one list's spot-doubled
# insertion forest, core/moves.MoveProblem) packs into two lane blocks:
#
#   nodes [D, 4, N_pad]:  mask, base_parent_slot (-1 root),
#                         cand_off, cand_cnt
#   cands [D, 3, K_pad]:  parent_slot, prio_hi, prio_lo
#
# Candidates are sorted per node by priority DESCENDING and concatenated
# in node-slot order (cand_off/cand_cnt index the runs), so "the node's
# current winner" is one gather at cand_off + ptr. prio_lo is the rank of
# the candidate's (actor, moved-id) pair in the realm's sorted pair
# table — integer comparisons reproduce the host tuple order exactly,
# and priorities stay UNIQUE (the cycle-drop rule requires it).

MOVE_NODE_FIELDS = ("node_mask", "base_parent", "cand_off", "cand_cnt")
MOVE_CAND_FIELDS = ("cand_parent", "cand_hi", "cand_lo")
MOVE_PRIO_PAD = np.iinfo(np.int32).max


def pack_moves(problems: list) -> dict:
    """Pack MoveProblems into the move-resolution lane layout. Returns
    {"nodes": [D, 4, N_pad] int32, "cands": [D, 3, K_pad] int32}."""
    from ..utils import metrics

    d = len(problems)
    n_max = max((len(p.nodes) for p in problems), default=0)
    k_max = max((sum(len(c) for c in p.cands) for p in problems), default=0)
    n_pad = pad_to_lanes(max(n_max, 1))
    k_pad = pad_to_lanes(max(k_max, 1))
    nodes = np.zeros((d, len(MOVE_NODE_FIELDS), n_pad), np.int32)
    nodes[:, 1, :] = -1
    cands = np.zeros((d, len(MOVE_CAND_FIELDS), k_pad), np.int32)
    cands[:, 0, :] = -1
    cands[:, 1:, :] = MOVE_PRIO_PAD
    for i, p in enumerate(problems):
        n = len(p.nodes)
        if n == 0:
            continue
        # RANK-compress both priority components: raw lamport sums can
        # exceed int32 on deep histories and a local unstamped preview
        # op carries a 2^62 "wins over everything" sentinel — ranks are
        # order-isomorphic, bounded by the candidate count, and can
        # never collide with the MOVE_PRIO_PAD sentinel
        hi_vals = sorted({c[0] for cl in p.cands for c in cl})
        hi_rank = {v: r for r, v in enumerate(hi_vals)}
        lo_pairs = sorted({c[1] for cl in p.cands for c in cl})
        lo_rank = {pair: r for r, pair in enumerate(lo_pairs)}
        nodes[i, 0, :n] = 1
        nodes[i, 1, :n] = np.asarray(p.base[:n], np.int32) if p.base else -1
        off = 0
        for s in range(n):
            cl = p.cands[s]
            nodes[i, 2, s] = off
            nodes[i, 3, s] = len(cl)
            for (hi, lo, parent, _op) in cl:
                cands[i, 0, off] = -1 if parent is None else parent
                cands[i, 1, off] = hi_rank[hi]
                cands[i, 2, off] = lo_rank[lo]
                off += 1
    metrics.bump("engine_move_tables_packed", d)
    return {"nodes": nodes, "cands": cands}
