"""Causally-stable compaction for the resident rows engine.

The reference never reclaims history: its OpSet appends forever
(/root/reference/src/op_set.js:250) and its only compaction analog is a
save/load round trip (/root/reference/src/automerge.js:223-226) that still
replays every change. A heap program degrades gradually under that growth;
the rows engine instead has a hard admission wall — `pack.rows_dims_fit`
bounds the reconcile kernels' VMEM working set, so a single long-lived document
(a year of keystrokes) marches monotonically into a typed budget error.
Compaction is the TPU-first answer: reclaim row slots whose ops can no
longer influence ANY future state, so the device working set tracks the
*visible* document size, not the length of its history.

What makes a slot reclaimable — and why the state hash cannot move:

- `kernels.state_hash` is a pure function of the visible state: it sums
  contributions from CANDIDATE ops only (survivors of the domination join
  that carry a value), keyed by (field content hash | owning-list object
  hash + visible rank, actor rank, value content hash). Nothing about
  dropped rows enters it.
- **Dominated assigns** are dead forever: domination is monotone (the
  dominator's change-clock covers them; no later change can revive them).
  Dropped unconditionally.
- **Non-assign rows** (make*/ins) are inert in the join — `is_assign =
  action >= A_SET` excludes them from survivor/candidate/present — their
  effect lives entirely in the list bands and object tables. Dropped
  unconditionally.
- **Surviving DEL ops** pin a field absent. Below the peer-clock floor they
  can go too: every future change's clock covers them, so the very first
  concurrent-with-nothing write to that field dominates them in the
  uncompacted replica and simply *wins vacuously* in the compacted one —
  identical visible outcome. Above the floor they stay (a genuinely
  concurrent assign may still arrive, and reference semantics make the
  assign win over the concurrent delete — dropping the DEL early would not
  change that winner, but it WOULD change `present` if no assign ever
  comes).
- **Tombstoned elements** can vacate their slot once (a) every op on the
  element's field is below the floor — every known peer has seen the
  tombstone, so no conforming peer will ever anchor an insert at it — and
  (b) no retained element anchors at it (anchor chains are kept closed so
  RGA sibling keys of retained elements never lose their comparison
  basis). Visible ranks of the remaining elements are unchanged by
  construction, so list hash contributions are unchanged.

The *clock floor* comes from the sync layer: `Connection` reports each
peer's advertised per-doc clock to the DocSet (`note_peer_clock`), and the
service takes the per-actor elementwise min across registered peers. With
no registered peers the floor is the doc's own clock — a standalone node
compacts freely, exactly like a single-user editor.

Admission after compaction is untouched: causal admission is clock-based
((actor, seq) against per-doc clock dicts, which compaction never shrinks),
so a change whose deps reference compacted-away history admits normally.
The authoritative change log is NOT touched here — `missing_changes`,
`materialize` and rebuild-from-log keep their full fidelity; bounding the
log's host-RAM growth is the separate log-horizon layer
(sync/logarchive.py + ResidentRowsDocSet.archive_log_prefix), which moves
the causally-stable prefix below the same floor into an append-only
archive with transparent cold reads for lagging peers.
"""

from __future__ import annotations

import numpy as np

from .encode import A_DEL, A_SET
from ..utils import metrics


def causal_floor(rset, i: int) -> dict[str, int]:
    """The causal-stability floor for doc i (Wuu-Bernstein stability): per
    actor b, the min over every actor a of F_a(b), where F_a is the
    transitive clock of a's newest admitted change plus a's own seq. Any
    conforming in-flight or future change from actor a carries a clock
    covering F_a (each change includes its predecessor), so everything at
    or below this floor is causally covered by ALL future ingress — a
    tombstone below it can never be anchored at, a DEL below it is
    dominated by any future assign to its field."""
    t = rset.tables[i]
    rset._sync_stale_table(t)
    clock = dict(t.clock)
    if not clock:
        return {}
    floor: dict[str, int] | None = None
    for a, s in clock.items():
        T = t.state_clocks.get((a, s))
        if T is None:
            return {}   # no frontier memo: stay conservative
        T = rset._memo_dict(t, (a, s))   # a lazy dense row becomes a dict
        F = dict(T)
        F[a] = max(F.get(a, 0), s)
        floor = F if floor is None else {
            b: min(floor.get(b, 0), F.get(b, 0)) for b in clock}
    return {b: v for b, v in floor.items() if v > 0}


def _floor_ranks(rset, i: int, floor: dict[str, int]) -> np.ndarray:
    """Floor seqs by actor rank in doc i's own basis (0 for actors the
    floor doesn't cover)."""
    out = np.zeros(rset.cap_actors, np.int64)
    rank = rset.tables[i].actor_rank
    for a, s in (floor or {}).items():
        r = rank.get(a)
        if r is not None:
            out[r] = int(s)
    return out


_OP_BANDS = (("om", 0), ("ac", -1), ("fid", -1), ("act", 0), ("seq", 0),
             ("chg", 0), ("fh", 0), ("vh", 0))
_ELEM_BANDS = (("im", 0), ("if", -1), ("ip", 0), ("io", -1))
# lanes compacted together: one gather of their columns, one keep mask
CHUNK = 256


def _op_keep_mask(b, cols, I: int, A: int, floor_r) -> tuple:
    """Keep mask [I, k] over the op slots of k lanes (`cols`: their dense
    columns, [rows, k]; `floor_r` [A, k]: each lane's floor by actor rank):
    candidates, plus above-floor DEL survivors. Returns it with the mask of
    the assigns whose field keeps an element's slot: the candidates, and
    every assign above the floor.

    Mirrors kernels.field_states' domination join on the host: op j
    dominates op i iff both assigns on the same field, j's change-clock at
    i's actor >= i's seq, and they come from different changes. Every
    lane's assigns are sorted by (lane, field), so the pairs of one field
    lie at distances below its count: one vectorized pass a distance, as
    many passes as the busiest field has assigns (a handful), not one a
    field nor one a lane.
    """
    om, ac, fid, act, seq, chg = (cols[b[g]:b[g] + I] for g in (
        "om", "ac", "fid", "act", "seq", "chg"))
    co = cols[b["co"]:b["co"] + A * I].reshape(A, I, -1)
    amask = (om != 0) & (ac >= A_SET)
    lane, slot = np.nonzero(amask.T)             # lane-major
    key = (lane.astype(np.int64) << 32) | fid[slot, lane].astype(np.int64)
    order = np.argsort(key, kind="stable")
    lane, slot, key = lane[order], slot[order], key[order]
    dominated = np.zeros(amask.shape, bool)
    for d in range(1, len(key)):
        same = np.flatnonzero(key[d:] == key[:-d])
        if not len(same):
            break      # every field has at most d assigns
        ln, si, sj = lane[same], slot[same], slot[same + d]
        other = chg[si, ln] != chg[sj, ln]
        hit = other & (co[act[si, ln], sj, ln] >= seq[si, ln])
        dominated[si[hit], ln[hit]] = True
        hit = other & (co[act[sj, ln], si, ln] >= seq[sj, ln])
        dominated[sj[hit], ln[hit]] = True
    lanes = np.arange(amask.shape[1])[None, :]
    above = seq > floor_r[np.clip(act, 0, A - 1), lanes]
    keep = amask & ~dominated & ~((ac == A_DEL) & ~above)
    return keep, (keep & (ac != A_DEL)) | (amask & above)


def _compact_lanes(rset, idxs: list, floors: list, pins: list) -> list:
    """Compact the documents at lanes `idxs` in place, each to its floor
    (`floors`, aligned), keeping the slots of its `pins` (element ids that
    must keep their slots regardless of the floor: anchors referenced by
    known-but-not-yet-admitted changes, a coalesced pending round, the
    un-replayed tail of a rebuild; the floor argument covers only changes
    *generated after* their sender saw the tombstone, not ones already in
    flight). One gather of the lanes' columns, one keep mask and one
    packing of the op bands for them all, each document's element reclaim
    on its own column, one linearization of every list they hold, and the
    columns that changed written back. Returns each lane's reclaim stats,
    `changed` among them.

    The caller owns invalidation (hash marks, the device copy) and
    native-encoder sync; use ResidentRowsDocSet.compact() rather than
    calling this directly.
    """
    b = rset._bases()
    I, A = rset.cap_ops, rset.cap_actors
    sel = np.asarray(idxs, np.int64)
    was = rset.rows_host[:, sel]                 # a gather: a dense copy
    cols = was.copy()
    floor_r = np.stack([_floor_ranks(rset, i, f)
                        for i, f in zip(idxs, floors)], axis=1)
    keep, slot_fids = _op_keep_mask(b, cols, I, A, floor_r)
    fid = cols[b["fid"]:b["fid"] + I]
    keep_fids = [set(fid[slot_fids[:, t], t].tolist())
                 for t in range(len(idxs))]

    # ---- rewrite the op bands: survivors packed to the front ----
    n_keep = keep.sum(axis=0)
    order = np.argsort(~keep, axis=0, kind="stable")
    live = np.arange(I)[:, None] < n_keep[None, :]
    for g, fill in _OP_BANDS:
        band = cols[b[g]:b[g] + I]
        band[:] = np.where(live, np.take_along_axis(band, order, 0), fill)
    co = cols[b["co"]:b["co"] + A * I].reshape(A, I, len(idxs))
    co[:] = np.where(live[None], np.take_along_axis(co, order[None], 1), 0)

    stats, lists = [], []
    for t, i in enumerate(idxs):
        n_ops0 = int(rset.op_count[i])
        rset.op_count[i] = rset.tables[i].n_ops = int(n_keep[t])
        n_elems = _reclaim_elems(rset, i, cols[:, t], b, keep_fids[t],
                                 pins[t])
        if n_elems is None:
            n_elems = (_slotted(rset, i),) * 2
        else:
            lists += [(i, lrow) for lrow in rset.ins_log[i]]
        stats.append({"ops_before": n_ops0, "ops_after": int(n_keep[t]),
                      "elems_before": n_elems[0], "elems_after": n_elems[1]})
    if lists:
        # fresh RGA positions for every list of a compacted document
        # (ghosts included in the linearization, rank-compressed over the
        # slotted entries)
        docs, prow, pval = rset._linearized_pos_rows(lists)
        col_of = {i: t for t, i in enumerate(idxs)}
        cols[prow, [col_of[d] for d in docs.tolist()]] = pval
    changed = (cols != was).any(axis=0)
    if changed.any():
        rset.rows_host[:, sel[changed]] = cols[:, changed]
    for s, c in zip(stats, changed.tolist()):
        s["changed"] = c
    return stats


def _slotted(rset, i: int) -> int:
    return sum(1 for e in rset.ins_log[i].values()
               for (s, _, _, _) in e if s >= 0)


def _reclaim_elems(rset, i: int, col, b, keep_fids: set, pins):
    """Element reclaim of document i on its dense column `col` (its op
    bands already packed): (slotted elements before, after), or None where
    queued changes may anchor anywhere and nothing is reclaimed.

    Host truth for elements is ins_log (slot, elem-counter, actor-rank,
    parent-slot per list row) plus the rows bands themselves; the eid is
    reconstructible as "actor:counter" (core/ids.make_elem_id — the same
    format both encoders intern) and the element's field id is read from
    the `if` band, so this pass works identically over the native and
    pure-Python encoders. `keep_fids`: the fields whose element keeps its
    slot (a candidate, or an assign above the floor), from the ORIGINAL
    ops."""
    t = rset.tables[i]
    if t.queue:
        return None
    from ..core.ids import make_elem_id

    E = rset.cap_elems
    n_elems0 = n_elems1 = 0
    for lrow, entries in list(rset.ins_log[i].items()):
        base = lrow * E
        fid_band = col[b["if"] + base:b["if"] + base + E].tolist()
        n = len(entries)
        n_slotted = sum(1 for (s, _, _, _) in entries if s >= 0)
        n_elems0 += n_slotted
        # keep_slot: the element keeps its device band slot — visible,
        # or some op on its field is still above the floor. A slotted
        # entry losing this becomes a GHOST: it keeps its RGA ordering
        # key in this host tree (its retained descendants and future
        # siblings of its parent still compare against that key) but
        # frees the band slot. Ghost entries with no tree-retained
        # child drop from the host tree entirely.
        keep_slot = [False] * n
        keep_tree = [False] * n
        has_kept_child: set[int] = set()
        for k in range(n - 1, -1, -1):
            slot, elem_c, arank_c, parent = entries[k]
            if slot >= 0:
                keep_slot[k] = (fid_band[slot] in keep_fids
                                or (bool(pins) and make_elem_id(
                                    t.actors[arank_c], elem_c) in pins))
            if keep_slot[k] or k in has_kept_child:
                keep_tree[k] = True
                if parent >= 0:
                    has_kept_child.add(parent)
        n_keep_slots = sum(keep_slot)
        n_elems1 += n_keep_slots
        if n_keep_slots == n_slotted and all(keep_tree):
            continue
        # rebuild the entry list: tree-retained entries in arrival
        # order; slots renumber densely over the slot-keeping ones so
        # the encoders' next-slot rule (len(elem_slots[obj])) keeps
        # assigning fresh slots past the compacted set
        idx_map: dict[int, int] = {}
        slot_remap: dict[int, int] = {}
        new_entries: list[tuple] = []
        for k in range(n):
            if not keep_tree[k]:
                continue
            slot, elem, arank, parent = entries[k]
            ns = -1
            if keep_slot[k]:
                ns = len(slot_remap)
                slot_remap[slot] = ns
            idx_map[k] = len(new_entries)
            new_entries.append(
                (ns, elem, arank, idx_map[parent] if parent >= 0 else -1))
        # every slotted entry that lost its slot (ghosted or fully
        # dropped) is a forbidden future anchor
        ghosts = rset.ghost_eids[i]
        for k in range(n):
            slot, elem, arank, _parent = entries[k]
            if slot >= 0 and not keep_slot[k]:
                ghosts.add(make_elem_id(t.actors[arank], elem))
        rset.ins_log[i][lrow] = new_entries
        rset.ins_idx[i][lrow] = {
            s: k for k, (s, _, _, _) in enumerate(new_entries) if s >= 0}
        oi = rset.list_obj[i].get(lrow)
        if oi is not None and t.elem_slots.get(oi):
            # pure-Python encoder path: its eid->slot map lives here
            eid_by_slot = {s: eid for eid, s in t.elem_slots[oi].items()}
            t.elem_slots[oi] = {eid_by_slot[s]: ns
                                for s, ns in slot_remap.items()}
        # rewrite this list's element bands: the kept slots, densely
        src = np.fromiter(slot_remap, np.int64, len(slot_remap))
        for g, fill in _ELEM_BANDS:
            band = col[b[g] + base:b[g] + base + E]
            kept = band[src]
            band[:] = fill
            band[:len(src)] = kept
    t.max_elems = max((sum(1 for (s, _, _, _) in e if s >= 0)
                       for e in rset.ins_log[i].values()), default=0)
    return n_elems0, n_elems1


def compact(rset, floors: dict[str, dict[str, int]],
            pins: dict[str, set] | None = None) -> dict[str, dict]:
    """Compact every doc in `floors` (doc_id -> clock floor) in place.
    `pins` maps doc_id -> element ids that must keep their slots (anchors
    of known-but-unadmitted changes; see _compact_lanes).

    Engine-level invalidation and native-encoder slot sync happen here, in
    O(the documents compacted), CHUNK lanes at a time: where the device
    copy is current, the lanes a compaction rewrote are written into it
    from the mirror (`_put_lanes`), so the copy stays current; `_elems_hi`
    is a high-water mark and stays (a compaction only frees slots, and the
    caps never shrink). Counts the documents whose slots moved
    (`rows_docs_compacted`).
    """
    rset._check_poisoned()
    todo = []
    for doc_id, floor in floors.items():
        rset.compaction_floors[doc_id] = dict(floor)
        i = rset.doc_index.get(doc_id)
        if i is not None:
            rset._sync_stale_table(rset.tables[i])
            todo.append((doc_id, i, floor, (pins or {}).get(doc_id)))
    stats: dict[str, dict] = {}
    rewritten = []
    for lo in range(0, len(todo), CHUNK):
        part = todo[lo:lo + CHUNK]
        got = _compact_lanes(rset, [p[1] for p in part],
                             [p[2] for p in part], [p[3] for p in part])
        for (doc_id, i, _floor, _pins), s in zip(part, got):
            if s.pop("changed"):
                rewritten.append(i)
            stats[doc_id] = s
            if rset._native is not None and (
                    s["ops_after"] < s["ops_before"]
                    or s["elems_after"] < s["elems_before"]):
                _sync_native_elem_slots(rset, i)
    if rewritten and rset._dev_current:
        rset._put_lanes(rewritten)
        metrics.bump("rows_lanes_put", len(rewritten))
    moved = sum(1 for s in stats.values()
                if s["ops_after"] < s["ops_before"]
                or s["elems_after"] < s["elems_before"])
    if moved:
        metrics.bump("rows_docs_compacted", moved)
    return stats


def _sync_native_elem_slots(rset, i: int) -> None:
    """Mirror doc i's renumbered element slots into the native encoder
    (DocState.elem_slots / max_elems in native/deltaenc.cpp): the C++ side
    assigns the next slot as len(elem_slots[obj]) and resolves insert
    anchors through that map, so it must see exactly the compacted view.
    The eid is rebuilt from the ins_log entry (core/ids.make_elem_id
    format, identical to the C++ interning key in deltaenc.cpp A_INS)."""
    from ..core.ids import make_elem_id

    objs, slots, eids = [], [], []
    for lrow, entries in rset.ins_log[i].items():
        oi = rset.list_obj[i][lrow]
        for (slot, elem, arank, _parent) in entries:
            if slot < 0:   # ghosts stay out of the encoder's maps
                continue
            objs.append(oi)
            slots.append(slot)
            eids.append(make_elem_id(rset.tables[i].actors[arank], elem))
    rset._native.reset_elem_slots(i, objs, slots, eids,
                                  rset.tables[i].max_elems)
