"""Fleet kind `lists`: long-lived lists whose items come and go (to-do lists,
queues, playlists), each written by its own devices. A list is the document

    {"items": ["an item", ...]}

written only by Automerge v0.8.0's list edits: `insertAt` (an `ins` of a new
element and a `set` of its value), `deleteAt` (a `del` of the element) and
the assignment of an item (a `set` of an element that is there). A deleted
element stays in the list's history as a tombstone (the reference never
reclaims one: `src/op_set.js`), so a list's history and its element slots
keep growing while what it shows stays short.

A configuration's `fleet` group holds `FleetSpec`'s numbers (`n_small`
lists take the traffic, `n_heavy` full documents of `heavy_ops` ops each do
not, `load_batch` lists a load round, `history_cap` the op rows the full
documents make the resident layout hold), and its `lists` group this kind's
own:

- `ops_at_load`, `visible_at_load`: a list's op rows at load are drawn
  uniformly from the first range, its visible items uniformly from the
  second and lowered until at least `tombstone_share` of its element slots
  are tombstones (about a seventh of its op rows at most);
- `full_slots`: a full document holds two lists, `items` with this many
  elements and `done` with the rest of its `heavy_ops`: at load the full
  documents set the resident caps (512 op rows, 4 actors, two lists of 256
  element slots) and take no traffic.

No cap stops a run: a list passes the resident op rows in the window, and
the program has to compact it (`request_changes` never returns the name of
a cap).

Its `writers` group: `devices` a list, each with a uuid-shaped id no other
list shares, and `concurrent_share`, as `fleets/devices.py` has it: with that
probability, where the list's latest change is another device's, the writer
has not seen it (its `deps` name the frontier before that change, and it
sees the list as it was then). Its `actions` group: the share of each of the
three actions, and `actions_max`: a change makes 1 to that many, uniformly.

The load of a list is its churn: changes of 1 to `actions_max` actions, each
by a device drawn uniformly that has seen every change before it, whose
actions are drawn so that the list ends with its drawn op rows and visible
items; then each device writes once more (one assignment), so that every
device's latest change covers the churn, as the engine's causal floor asks.

A window change is 1 to `actions_max` actions by a device drawn uniformly:

- `insert_at`: `ins` after an anchor drawn uniformly over the head and the
  items the writer sees, and `set` of the new element's value;
- `delete_at`: `del` of an item the writer sees, drawn uniformly;
- `set_item`: `set` of such an item, to a new value.

A `delete_at` or a `set_item` where the writer sees no item it has not
assigned in this change becomes an `insert_at`: no change assigns one key
twice. An element's counter is one more than the largest the writer has
seen in the list. A request's draws a list (writer, concurrency, count, and
two for each action) come from a generator seeded by what the schedule
drew, and an item's value is the schedule's value, so `request_changes` is a
function of the fleet's state and the request, and `replay` makes every
change again from the seed.

The fleet keeps, exactly, which lists the window gave concurrent inserts at
one anchor (`anchored`), and which an insert anchored at an element that the
change it had not seen deleted (`reanchored`: a tombstone above any floor,
which a compaction has to keep).
"""

from __future__ import annotations

import hashlib
import random
import zlib
from dataclasses import dataclass

import numpy as np

from automerge_tpu.core.change import Change, Op

import fleet as base

ROOT_ID = "00000000-0000-0000-0000-000000000000"
HEAD = "_head"
BASE_OPS = 2          # makeList, link: a list's base change


@dataclass
class Spec:
    n_lists: int
    history_cap: int
    ops_at_load: tuple = (96, 480)
    visible_at_load: tuple = (16, 96)
    tombstone_share: float = 0.6
    full_slots: int = 132
    n_full: int = 4
    full_ops: int = 508
    load_batch: int = 1_000
    devices: int = 4
    concurrent_share: float = 0.10
    insert_at: float = 0.45
    delete_at: float = 0.45
    set_item: float = 0.10
    actions_max: int = 4

    @classmethod
    def from_config(cls, config: dict) -> "Spec":
        """`fleet` holds `FleetSpec`'s numbers: the lists are `n_small`,
        the full documents `n_heavy` of `heavy_ops` ops each; a list fleet
        has no list, text or move documents of fleet10k's and no edits
        behind its load. `lists`, `writers` and `actions` hold this kind's
        own."""
        fleet = base.FleetSpec.from_config(config)
        if fleet.n_list or fleet.n_text or fleet.n_move \
                or fleet.history_changes_max:
            raise ValueError("a list fleet has no list, text or move "
                             "documents and no edits behind its load")
        own = dict(config["lists"])
        for k in ("ops_at_load", "visible_at_load"):
            own[k] = tuple(own[k])
        spec = cls(n_lists=fleet.n_small, n_full=fleet.n_heavy,
                   full_ops=fleet.heavy_ops, history_cap=fleet.history_cap,
                   load_batch=fleet.load_batch, **own,
                   **config["writers"], **config["actions"])
        rest = spec.full_ops - 2 * BASE_OPS - 2 * spec.full_slots
        if rest < 2 or rest % 2:
            raise ValueError(f"heavy_ops {spec.full_ops} is no full "
                             f"document's op count")
        if abs(spec.insert_at + spec.delete_at + spec.set_item - 1) > 1e-9:
            raise ValueError("the actions' shares do not sum to 1")
        return spec


def actor_id(seed: int, doc: str, k: int) -> str:
    """Device `k` of a list: 32 hex digits, as a uuid without its dashes;
    no two lists share one (the scheme of `fleets/devices.py`)."""
    return hashlib.blake2b(f"{int(seed)}/{doc}/{k}".encode(),
                           digest_size=16).hexdigest()


class ListDoc:
    """What the fleet keeps of one list to write its next change: its
    devices and their seqs, the frontier, the visible elements in the order
    they were made, the largest counter, and of the latest change what a
    writer that has not seen it must not see."""
    __slots__ = ("index", "obj", "devices", "seqs", "heads", "heads_before",
                 "last", "alive", "max_elem", "slots", "depth", "before_max",
                 "last_added", "last_deleted", "last_ins")

    def __init__(self, index: int, obj: str, devices: list):
        self.index, self.obj, self.devices = index, obj, devices
        self.seqs = [0] * len(devices)
        self.heads: dict = {}
        self.heads_before: dict = {}
        self.last = -1
        self.alive: list = []
        self.max_elem = 0
        self.slots = 0
        self.depth = 0
        self.before_max = 0
        self.last_added: frozenset = frozenset()
        self.last_deleted: tuple = ()
        self.last_ins: frozenset = frozenset()


class Fleet:
    dims_fixed = True
    # the schedule's `fields` draw is not read: what a change does is drawn
    # by the fleet's own generator, seeded by the request
    n_fields = 1

    def __init__(self, spec: Spec, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.small = [f"list{i:05d}" for i in range(spec.n_lists)]
        self.structured = [f"full{i:02d}" for i in range(spec.n_full)]
        self._id_prefix = f"{zlib.crc32(str(self.seed).encode()):08x}"
        self.lists: dict = {}
        self.anchored: set = set()
        self.reanchored: set = set()
        self.loaded = False
        self.first = {d: self._load_full(d, spec.n_lists + i)
                      for i, d in enumerate(self.structured)}

    @property
    def doc_ids(self) -> list:
        return self.structured + self.small

    def _obj(self, index: int, kind: int) -> str:
        """A uuid-shaped object id: the fleet's seed, the document, a kind."""
        return (f"{self._id_prefix}-{kind:04x}-4000-8000-{index:012x}")

    def _new(self, d: str, index: int, kind: int = 1) -> ListDoc:
        L = ListDoc(index, self._obj(index, kind),
                    [actor_id(self.seed, d, k)
                     for k in range(self.spec.devices)])
        self.lists[d] = L
        return L

    # -- the load -----------------------------------------------------------

    def _load_full(self, d: str, index: int) -> list:
        """A full document: a base change of two lists, then each device
        appends its share of both lists' elements, one after another."""
        spec = self.spec
        L = self._new(d, index)
        done = self._obj(index, 2)
        ops = [Op("makeList", L.obj), Op("link", ROOT_ID, key="items",
                                          value=L.obj),
               Op("makeList", done), Op("link", ROOT_ID, key="done",
                                        value=done)]
        parts = [[0, ops]]                  # [device, ops] of each change
        n_done = (spec.full_ops - 2 * BASE_OPS) // 2 - spec.full_slots
        k = 0
        for obj, n in ((L.obj, spec.full_slots), (done, n_done)):
            prev = HEAD
            for j in range(n):
                w = (j * spec.devices) // n
                k += 1
                eid = f"{L.devices[w]}:{k}"
                if parts[-1][0] != w:
                    parts.append([w, []])
                parts[-1][1] += [Op("ins", obj, key=prev, elem=k),
                                 Op("set", obj, key=eid, value=f"item {k}")]
                prev = eid
        changes = []
        for w, ops in parts:
            L.seqs[w] += 1
            deps = {changes[-1].actor: changes[-1].seq} if changes else {}
            changes.append(Change(L.devices[w], L.seqs[w], deps, ops))
        L.depth = sum(len(c.ops) for c in changes)
        L.heads = {changes[-1].actor: changes[-1].seq}
        L.last = L.devices.index(changes[-1].actor)
        return changes

    def _load_list(self, d: str, index: int) -> list:
        """A list's load: its base change, the churn, and one assignment by
        each device after it."""
        spec = self.spec
        rng = random.Random(f"{self.seed}/{d}")
        L = self._new(d, index)
        rows = rng.randint(*spec.ops_at_load) - BASE_OPS - spec.devices
        visible = rng.randint(*spec.visible_at_load)
        left = _plan(rows, visible)
        while left["del"] < spec.tombstone_share * left["ins"]:
            visible -= 1
            left = _plan(rows, visible)
        changes = [Change(L.devices[0], 1, {}, [
            Op("makeList", L.obj),
            Op("link", ROOT_ID, key="items", value=L.obj)])]
        L.seqs[0] = 1
        L.heads = {L.devices[0]: 1}
        L.last = 0
        while sum(left.values()):
            w = rng.randrange(spec.devices)
            kinds = []
            for _ in range(rng.randint(1, spec.actions_max)):
                total = sum(left.values())
                if not total:
                    break
                pick = rng.random() * total
                kind = "ins" if pick < left["ins"] else \
                    "del" if pick < left["ins"] + left["del"] else "set"
                left[kind] -= 1
                kinds.append(kind)
            changes.append(self._churn(L, w, kinds, rng, left))
        for w in range(spec.devices):
            changes.append(self._churn(L, w, ["set"], rng, left))
        L.depth = sum(len(c.ops) for c in changes)
        return changes

    def _churn(self, L: ListDoc, w: int, kinds: list, rng, left: dict):
        """A load change by device `w` that has seen every change before
        it. An action with nothing to act on is an insert, and the counts
        `left` are squared: the list still ends as drawn."""
        actor = L.devices[w]
        seq = L.seqs[w] = L.seqs[w] + 1
        alive = L.alive
        ops: list = []
        touched: set = set()
        n_touched = 0           # items of `alive` this change assigned
        top = L.max_elem
        for kind in kinds:
            if kind != "ins" and len(alive) <= n_touched:
                # an insert in its place; one of the inserts left becomes
                # the action drawn, where one is left
                if kind == "del" and left["ins"]:
                    left["ins"] -= 1
                    left["del"] += 1
                elif kind == "set" and left["ins"]:
                    left["ins"] -= 1
                    left["set"] += 1
                kind = "ins"
            if kind == "ins":
                k = rng.randrange(len(alive) + 1)
                anchor = alive[k - 1] if k else HEAD
                top += 1
                eid = f"{actor}:{top}"
                ops += [Op("ins", L.obj, key=anchor, elem=top),
                        Op("set", L.obj, key=eid, value=f"v{top}")]
                alive.append(eid)
                L.slots += 1
                touched.add(eid)
                n_touched += 1
            else:
                eid = alive[rng.randrange(len(alive))]
                while eid in touched:
                    eid = alive[rng.randrange(len(alive))]
                touched.add(eid)
                if kind == "del":
                    ops.append(Op("del", L.obj, key=eid))
                    alive.remove(eid)
                else:
                    ops.append(Op("set", L.obj, key=eid,
                                  value=f"s{seq}.{len(ops)}"))
                    n_touched += 1
        L.before_max, L.max_elem = L.max_elem, max(L.max_elem, top)
        deps = {a: s for a, s in L.heads.items() if a != actor}
        L.heads_before, L.heads = L.heads, {actor: seq}
        L.last = w
        return Change(actor, seq, deps, ops)

    def load_rounds(self):
        """The load, one coalesced round at a time: the full documents
        first (they set the resident caps), then `load_batch` lists a
        round."""
        yield self.first
        yield from self.list_load_rounds()
        self.loaded = True

    def list_load_rounds(self):
        spec = self.spec
        for lo in range(0, spec.n_lists, spec.load_batch):
            yield {d: self._load_list(d, lo + i)
                   for i, d in enumerate(self.small[lo:lo + spec.load_batch])}

    # -- one change ---------------------------------------------------------

    def _write(self, d: str, u: list, value: str) -> Change:
        """The list's next change from its uniform draws `u` (writer,
        concurrency, count, then kind and target of each action)."""
        spec, L = self.spec, self.lists[d]
        w = int(u[0] * len(L.devices))
        seen_all = not (L.last != w and u[1] < spec.concurrent_share)
        actor = L.devices[w]
        seq = L.seqs[w] = L.seqs[w] + 1
        if seen_all:
            seen = list(L.alive)
            top = L.max_elem
        else:
            # the list as it was before its latest change
            seen = [e for e in L.alive if e not in L.last_added] \
                + list(L.last_deleted)
            top = L.before_max
        L.before_max = L.max_elem
        ops: list = []
        touched: set = set()
        added, deleted, anchors = [], [], []
        for j in range(1 + int(u[2] * spec.actions_max)):
            pick, at = u[3 + 2 * j], u[4 + 2 * j]
            can = [e for e in seen if e not in touched]
            if pick < spec.insert_at or not can:
                k = int(at * (len(seen) + 1))
                anchor = seen[k - 1] if k else HEAD
                top += 1
                eid = f"{actor}:{top}"
                ops += [Op("ins", L.obj, key=anchor, elem=top),
                        Op("set", L.obj, key=eid, value=f"{value}.{j}")]
                seen.append(eid)
                L.alive.append(eid)
                L.slots += 1
                touched.add(eid)
                added.append(eid)
                anchors.append(anchor)
                if not seen_all:
                    if anchor in L.last_ins:
                        self.anchored.add(d)
                    if anchor in L.last_deleted:
                        self.reanchored.add(d)
            else:
                eid = can[int(at * len(can))]
                touched.add(eid)
                if pick < spec.insert_at + spec.delete_at:
                    ops.append(Op("del", L.obj, key=eid))
                    seen.remove(eid)
                    if eid in L.alive:
                        L.alive.remove(eid)
                        deleted.append(eid)
                else:
                    ops.append(Op("set", L.obj, key=eid,
                                  value=f"{value}.{j}"))
                    if eid not in L.alive:
                        # a concurrent assignment outlives the deletion
                        # it had not seen: the item shows again
                        L.alive.append(eid)
        L.max_elem = max(L.max_elem, top)
        before = L.heads if seen_all else L.heads_before
        deps = {a: s for a, s in before.items() if a != actor}
        if seen_all:
            heads = {actor: seq}
        else:
            latest = L.devices[L.last]
            heads = {latest: L.heads[latest], actor: seq}
        L.heads_before, L.heads = L.heads, heads
        L.last = w
        L.last_added = frozenset(added)
        L.last_deleted = tuple(deleted)
        L.last_ins = frozenset(anchors)
        L.depth += len(ops)
        return Change(actor, seq, deps, ops)

    # -- what run.py and the drivers ask of a fleet -------------------------

    def request_changes(self, drawn: tuple) -> dict:
        """{list id: [Change]} of one request as the schedule drew it: one
        change a list, by one of its own devices. Never the name of a cap:
        a list's history grows without end, and the program compacts it."""
        docs, _fields, values = drawn
        small = self.small
        idx = docs.tolist()
        u = np.random.default_rng(
            [self.seed, 0x11575, len(idx), int(docs.sum()),
             int(values.sum())]).random(
                (len(idx), 3 + 2 * self.spec.actions_max)).tolist()
        write = self._write
        return {small[i]: [write(small[i], ui, f"t{v}")]
                for i, v, ui in zip(idx, values.tolist(), u)}

    @staticmethod
    def request_ops(round_: dict) -> int:
        return sum(len(c.ops) for chs in round_.values() for c in chs)

    def replay(self, schedule, numbers) -> tuple:
        """Every acknowledged change made again from the seed on a fleet of
        its own: {list id: [Change]} of the load and the requests `numbers`
        in the order they were sent, and {(list id, actor, seq): request
        number} of the requests' changes."""
        again = type(self)(self.spec, self.seed)
        sent = {d: list(chs) for d, chs in again.first.items()}
        for round_ in again.list_load_rounds():
            sent.update(round_)
        again.loaded = True
        origin = {}
        for r in numbers:
            for d, chs in again.request_changes(schedule.request(r)).items():
                sent[d].extend(chs)
                origin[(d, chs[0].actor, chs[0].seq)] = r
        return sent, origin

    def load_line(self) -> dict:
        lists = [self.lists[d] for d in self.small]
        depths = sorted(L.depth for L in lists)
        visible = sorted(len(L.alive) for L in lists)
        dead = sorted(1 - len(L.alive) / max(L.slots, 1) for L in lists)
        return {"list_ops_min_median_max": [
                    depths[0], depths[len(depths) // 2], depths[-1]],
                "visible_min_median_max": [
                    visible[0], visible[len(visible) // 2], visible[-1]],
                "tombstone_share_min_median": [
                    round(dead[0], 3), round(dead[len(dead) // 2], 3)],
                "ops": sum(depths),
                "full_ops": [self.lists[d].depth for d in self.structured],
                "actor_ids": self.spec.devices * len(self.lists),
                "changes": sum(sum(L.seqs) for L in self.lists.values())}


def _plan(rows: int, visible: int) -> dict:
    """A churn's actions: inserts I, deletes D and assignments X (a tenth
    of the actions) with I - D = `visible` and 2I + D + X = `rows`, or the
    least over it."""
    n = visible
    while True:
        x = n // 10
        m = n - x
        if m >= visible and (m - visible) % 2 == 0 \
                and m + visible + (m - visible) // 2 + x >= rows:
            return {"ins": (m + visible) // 2, "del": (m - visible) // 2,
                    "set": x}
        n += 1


def make(config: dict, seed: int) -> Fleet:
    return Fleet(Spec.from_config(config), seed)
