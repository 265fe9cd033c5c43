"""A list's host state in the rows engine: one store, one record per
(document, list row).

The device holds a list's element bands; the host holds what orders them.
A record keeps the list's object interning index (`obj`, which keys the
encoders' element-slot maps), the content hash of its object id (`hash`,
the `io` band's value), the object id as the ingress names it (`oid`), and:

- its insert log, an int32 row an insert in arrival order (slot, element
  counter, actor rank, parent entry), its capacity doubling. The parent is
  the anchor's ENTRY index (-1: the head): compaction leaves GHOST entries
  (slot -1) that keep their RGA ordering key but free their band slot;
- entry-of-slot (`eos`) over its `ns` slots, dense from 0, and `hi`, an
  upper bound of its element counters;
- `ghosts`, the element ids of THIS list that compaction reclaimed: every
  known peer saw their tombstones, so an ingress anchored at one is
  refused before admission. Element counters belong to a list: two lists
  of a document can both hold `A:3`, and a ghost of one refuses no insert
  into the other.
"""

from __future__ import annotations

import numpy as np

from ..core.ids import HEAD, make_elem_id

# inserts a list may take in one round and still be placed against the
# mirror's positions (`placed`: a pass over the list's cells an insert); a
# list that takes more (a load, a paste) is re-linearized, which costs its
# entries once
PLACE_MAX = 64


class CompactionAnchorError(RuntimeError):
    """An ingress inserts after an element that compaction reclaimed. The
    clock floor guarantees every known peer saw that element's tombstone, so
    a conforming frontend can never emit this anchor (it only anchors at
    elements visible in its own state); the sender is either below the
    compaction horizon (needs a full resync) or nonconforming. Raised
    BEFORE admission — the node is untouched. The rejection is
    deterministic: the sync service drops the offending doc's round
    (`doc_id` below) instead of re-queueing it."""

    def __init__(self, msg: str, *, doc_id: str | None = None):
        super().__init__(msg)
        self.doc_id = doc_id


def ins_anchors(part, lo: int = 0, hi: int | None = None) -> list:
    """(list object id, anchor key) of each insert of an ingress: Change
    objects (a list of them, or a ChangesPart) or wire columns, of which
    it reads ops [lo, hi)."""
    if not hasattr(part, "op_action"):
        return [(op.obj, op.key) for c in getattr(part, "changes", part)
                for op in c.ops if op.action == "ins"]
    from ..storage import _ACTION_IDX
    j = lo + np.flatnonzero(
        np.asarray(part.op_action[lo:hi]) == _ACTION_IDX["ins"])
    objs, keys = part.objects, part.keys
    return [(objs[o], keys[k]) for o, k in zip(
        np.asarray(part.op_obj)[j].tolist(),
        np.asarray(part.op_key)[j].tolist()) if k >= 0]


def anchor_pins(parts_by_doc: dict) -> dict[str, set]:
    """The element ids each document's parts (each as ins_anchors reads
    it) insert after, the head aside: compaction must not reclaim these,
    since the round was made before its sender could have seen a floor
    past them (it is already in flight)."""
    pins = {d: {k for part in parts for _, k in ins_anchors(part)
                if k and k != HEAD} for d, parts in parts_by_doc.items()}
    return {d: p for d, p in pins.items() if p}


def _grown(a: np.ndarray, need: int) -> np.ndarray:
    """`a` in a buffer of at least `need` rows, twice its length or more
    (compaction may leave a list with none)."""
    out = np.empty((max(8, 2 * len(a), need),) + a.shape[1:], a.dtype)
    out[:len(a)] = a
    return out


class _List:
    __slots__ = ("obj", "hash", "oid", "log", "n", "eos", "ns", "hi",
                 "ghosts")

    def __init__(self, obj: int, objhash: int, oid: str):
        self.obj, self.hash, self.oid = obj, objhash, oid
        self.log, self.n = np.empty((8, 4), np.int32), 0
        self.eos, self.ns = np.empty(8, np.int32), 0
        self.hi = 0
        self.ghosts: set = set()


class ListStore:
    """Every list of a rows engine's documents, by (document, list row)."""

    def __init__(self, n_docs: int = 0):
        self._docs: list[list[_List]] = [[] for _ in range(n_docs)]
        self.ghosted: set[int] = set()   # documents holding a ghost

    def add_docs(self, n: int) -> None:
        self._docs.extend([] for _ in range(n))

    def add(self, i: int, lrow: int, obj: int, objhash: int,
            oid: str) -> None:
        """Document i's new list (rows dense in creation order)."""
        if lrow != len(self._docs[i]):
            raise RuntimeError(f"list row {lrow} of doc {i} out of order")
        self._docs[i].append(_List(obj, objhash, oid))

    def rows(self, i: int) -> range:
        return range(len(self._docs[i]))

    def obj(self, i: int, lrow: int) -> int:
        return self._docs[i][lrow].obj

    def log(self, i: int, lrow: int) -> np.ndarray:
        """The list's insert log [n, 4], a view until its next write."""
        r = self._docs[i][lrow]
        return r.log[:r.n]

    def ghosts(self, i: int, oid: str | None = None) -> set:
        """The ghosts of document i's list `oid`, or of all its lists."""
        return set().union(*(r.ghosts for r in self._docs[i]
                             if oid is None or r.oid == oid))

    def n_slotted(self, i: int) -> int:
        return sum(r.ns for r in self._docs[i])

    def max_slotted(self, i: int) -> int:
        return max((r.ns for r in self._docs[i]), default=0)

    def elem_ids(self, i: int, lrow: int, actors: list) -> list:
        """The element id of each entry of a list (`actors` by rank)."""
        log = self.log(i, lrow)
        return [make_elem_id(actors[a], e)
                for e, a in zip(log[:, 1].tolist(), log[:, 2].tolist())]

    def append(self, ins: np.ndarray):
        """Append a round's inserts, `ins` [k, 7] in admission order (doc,
        list row, slot, element counter, actor rank, anchor slot or -1,
        field id) as both encoders emit them. Returns (lid, io, lists, ok):
        each insert's index among the lists touched and its `io` cell;
        per list touched, (doc, list row, slotted entries before the round)
        [m, 3] and whether it can be `placed`: each insert its newest
        element (counter above every counter it holds), in the next slot,
        anchored at the head or at a slotted entry, at most PLACE_MAX."""
        docs = self._docs
        # list -> [index, doc, list row, slotted before, inserts, placeable]
        touched: dict = {}
        lid, io = [], []
        for (d, lrow, slot, elem, arank, ps, _fid) in ins.tolist():
            r = docs[d][lrow]
            st = touched.get(r)
            if st is None:
                st = touched[r] = [len(touched), d, lrow, r.ns, 0, True]
            st[4] += 1
            n, ns = r.n, r.ns
            if st[5] and not (elem > r.hi and slot == ns and ps < ns
                              and st[4] <= PLACE_MAX):
                st[5] = False
            if elem > r.hi:
                r.hi = elem
            if n == len(r.log):
                r.log = _grown(r.log, n + 1)
            if slot >= len(r.eos):
                r.eos = _grown(r.eos, slot + 1)
            r.log[n] = (slot, elem, arank, r.eos[ps] if ps >= 0 else -1)
            r.eos[slot] = n
            r.n, r.ns = n + 1, ns + 1
            io.append(r.hash)
            lid.append(st[0])
        lists = np.array([st[1:4] for st in touched.values()], np.int64)
        ok = np.fromiter((st[5] for st in touched.values()), bool,
                         len(touched))
        return (np.asarray(lid, np.int64), np.asarray(io, np.int64),
                lists, ok)

    def linearized(self, lists, ip: int, E: int):
        """Fresh RGA positions of `lists` ((doc, list row) pairs) from
        their logs, in one native call (linearize_lists): (docs, `ip`-band
        rows (its first row `ip`, E slots a list), positions). Ghosts order
        their descendants but ship no row: positions are rank-compressed
        over the slotted entries, dense in [0, E)."""
        from ..native.linearize import linearize_lists
        key = np.array(lists, np.int64).reshape(-1, 2)
        recs = [self._docs[d][lrow] for d, lrow in key.tolist()]
        lens = np.fromiter((r.n for r in recs), np.int64, len(recs))
        starts = np.zeros(len(recs) + 1, np.int64)
        np.cumsum(lens, out=starts[1:])
        ent = (np.concatenate([r.log[:r.n] for r in recs]) if recs
               else np.zeros((0, 4), np.int32))
        slots = ent[:, 0].astype(np.int64)
        pos = linearize_lists(ent[:, 1], ent[:, 2], ent[:, 3], starts)
        docs = np.repeat(key[:, 0], lens)
        lrows = np.repeat(key[:, 1], lens)
        slotted = slots >= 0
        if not slotted.all():
            keep = np.flatnonzero(slotted)
            owner = np.repeat(np.arange(len(recs)), lens)[keep]
            order = np.lexsort((pos[keep], owner))
            dense = np.empty(len(keep), np.int64)
            dense[order] = (np.arange(len(keep))
                            - np.searchsorted(owner[order], owner[order]))
            pos, slots = dense, slots[keep]
            docs, lrows = docs[keep], lrows[keep]
        return docs, ip + lrows * E + slots, pos

    @staticmethod
    def placed(mirror: np.ndarray, ip: int, E: int, lists: np.ndarray,
               lid: np.ndarray, parent: np.ndarray):
        """Positions of the lists `append` found placeable. RGA orders
        siblings by descending (counter, actor), so such an insert is its
        anchor's first child: on the dense ranks over the slotted entries
        it takes pos(anchor) + 1 (0 at the head), and every slotted entry
        at or past that rank moves up one. The ranks are read out of the
        `ip` band of `mirror` and placed in one native call (place_lists).
        lists: (doc, list row, slotted entries before the round) a list;
        lid, parent: each insert's list and anchor slot (-1: the head).
        Returns (docs, `ip`-band rows, positions) of the cells that moved
        and of the new slots."""
        from ..native.linearize import place_lists
        ins_off = np.zeros(len(lists) + 1, np.int64)
        np.cumsum(np.bincount(lid, minlength=len(lists)), out=ins_off[1:])
        return place_lists(
            mirror, lists[:, 0], ip + lists[:, 1] * E, lists[:, 2], ins_off,
            parent[np.argsort(lid, kind="stable")])

    def remap_ranks(self, i: int, perm: np.ndarray, n_old: int) -> None:
        """A registration moved document i's writers: rank a < n_old
        becomes perm[a] in each of its logs."""
        for r in self._docs[i]:
            a = r.log[:r.n, 2]
            a[:] = np.where(a < n_old, perm[np.minimum(a, n_old - 1)], a)

    def reclaim(self, i: int, lrow: int, keep_slot: np.ndarray,
                actors: list) -> np.ndarray | None:
        """Compaction's rewrite of a list: `keep_slot` (an entry of its log)
        names the entries that keep their band slot. A slotted entry that
        loses it stays in the tree as a ghost while a retained entry
        descends from it, else leaves; either way its element id joins the
        list's ghosts. Retained entries keep arrival order, their slots
        renumbered densely, so the encoders' next slot (len(elem_slots))
        lands past them. Returns the old slot of each new slot; None where
        nothing changes."""
        r = self._docs[i][lrow]
        log = r.log[:r.n]
        tree, par = keep_slot.tolist(), log[:, 3].tolist()
        for k in range(r.n - 1, -1, -1):
            if tree[k] and par[k] >= 0:
                tree[par[k]] = True
        slotted = log[:, 0] >= 0
        if all(tree) and (keep_slot == slotted).all():
            return None
        lost = np.flatnonzero(slotted & ~keep_slot).tolist()
        r.ghosts.update(make_elem_id(actors[a], e) for e, a in zip(
            log[lost, 1].tolist(), log[lost, 2].tolist()))
        self.ghosted.add(i)
        tree = np.asarray(tree, bool)
        new_of = np.cumsum(tree) - 1
        ent = log[tree]                       # a gather: a fresh array
        ent[:, 3] = np.where(ent[:, 3] >= 0,
                             new_of[np.maximum(ent[:, 3], 0)], -1)
        ks = keep_slot[tree]
        src = ent[ks, 0].copy()
        ent[:, 0] = np.where(ks, np.cumsum(ks) - 1, -1)
        r.log, r.n = ent, len(ent)
        r.eos, r.ns = np.flatnonzero(ks).astype(np.int32), len(src)
        return src

    def encoder_slots(self, i: int, actors: list):
        """Document i's slotted elements as an encoder's element-slot maps
        hold them: (object indices, slots, element ids)."""
        objs, slots, eids = [], [], []
        for lrow, r in enumerate(self._docs[i]):
            on = r.log[:r.n, 0] >= 0
            slots += r.log[:r.n][on, 0].tolist()
            objs += [r.obj] * int(on.sum())
            eids += [e for e, s in zip(self.elem_ids(i, lrow, actors),
                                       on.tolist()) if s]
        return objs, slots, eids

    def check(self, i: int, doc_id: str, part, lo: int = 0,
              hi: int | None = None) -> None:
        """Refuse, BEFORE admission, an ingress into document i that
        inserts after a ghost of the list it inserts into
        (CompactionAnchorError); `part` as ins_anchors reads it."""
        if i not in self.ghosted:
            return
        lists = self._docs[i]
        for oid, key in ins_anchors(part, lo, hi):
            if any(r.oid == oid and key in r.ghosts for r in lists):
                raise CompactionAnchorError(
                    f"insert anchored at compacted element {key!r} in doc "
                    f"{doc_id!r}; the sender is below the compaction "
                    f"horizon — full resync required", doc_id=doc_id)
