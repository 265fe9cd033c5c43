"""Fleet kind `boards`: card boards (Trello-like), each written by its own
devices. A board is the nested document of `bench.py` `gen_trellis` and of
the v0.8.0 README's cards example:

    {"board": {"lists": [{"title": "todo", "cards": [{"title", "done"}, ...]},
                         {"title": "done", "cards": [...]}]}}

three lists (the columns list and a `cards` list a column) and a map a card.

A configuration's `fleet` group holds `FleetSpec`'s numbers (`n_small`
boards take the traffic, `n_heavy` full boards of `heavy_ops` ops each do
not, `load_batch` boards a load round, `history_cap`), and its `boards`
group this kind's own:

- `columns`, `cards_at_load`: each of a board's devices adds that many
  cards at load (`gen_trellis`'s 5), each to a column drawn uniformly, and
  checks one of them off, in one change a device (a device that worked
  offline and syncs once), all of them concurrent after the board's base
  change. No change assigns one key twice: the card checked off is made
  with `done` true;
- `elem_cap`: the element slots a list may hold. `request_changes` returns
  `"history_cap"` / `"elem_cap"` before a board drawn would pass either cap.

A full board's devices put all their cards into the first column, so that
the resident op, actor and element caps are set by the load and stay fixed.

Its `writers` group: `devices` a board, each with a uuid-shaped id no other
board shares, and `concurrent_share`, as `fleets/devices.py` has it: with
that probability, where the board's latest change is another device's, the
writer has not seen it (its `deps` name the frontier before that change, and
it sees the board as it was then). Its `actions` group: the share of each of
a change's three actions.

A window change is one action by a device drawn uniformly:

- `add_card`: `ins` at an anchor drawn uniformly over the head and the
  column's visible cards, `makeMap`, `set` title, `set` done false, `link`;
- `mark_done`: one `set` of a card's `done` to the opposite of what was
  last written to it, the card drawn over the board's visible cards;
- `reorder`: Automerge v0.8.0 has no move op, so a card is dragged by a
  `del` of its element and a new card inserted at the target (a column drawn
  uniformly, an anchor uniformly), with the old card's title and done. Two
  concurrent reorders of one card leave two copies: that is the semantics.

An element's counter is one more than the largest the writer has seen in
that list. Counters are a list's own, so one device names elements of both
columns alike: the fleet knows a card by its column and its element. A request's six draws a board (writer, concurrency, action, card,
column, anchor) come from a generator seeded by what the schedule drew, and
a new card's title is the schedule's value, so `request_changes` is a
function of the fleet's state and the request, and `replay` makes every
change again from the seed.

The fleet keeps, exactly, which boards the window gave concurrent inserts at
one anchor (`anchored`; every board holds some from the load, where the
devices' first cards meet at the head of a column), a duplicated card
(`duplicated`: a reorder of an element the concurrent change had already
deleted by a reorder), and a tombstone beside a live sibling (`tombstoned`:
a reorder deleted an element while another under the same anchor was
visible): the check widens its sample by the first two.
"""

from __future__ import annotations

import random
import zlib
from dataclasses import dataclass

import numpy as np

from automerge_tpu.core.change import Change, Op

import fleet as base

ROOT_ID = "00000000-0000-0000-0000-000000000000"
HEAD = "_head"


@dataclass
class Spec:
    n_boards: int
    history_cap: int
    elem_cap: int
    n_full: int = 4
    full_cards: int = 10
    columns: int = 2
    cards_at_load: int = 5
    load_batch: int = 1_000
    devices: int = 8
    concurrent_share: float = 0.10
    add_card: float = 0.4
    mark_done: float = 0.4
    reorder: float = 0.2

    @classmethod
    def from_config(cls, config: dict) -> "Spec":
        """`fleet` holds `FleetSpec`'s numbers: the boards are `n_small`,
        the full boards `n_heavy` of `heavy_ops` ops each; a board fleet
        has no list, text or move documents of fleet10k's and no edits
        behind its load. `boards`, `writers` and `actions` hold this
        kind's own."""
        fleet = base.FleetSpec.from_config(config)
        if fleet.n_list or fleet.n_text or fleet.n_move \
                or fleet.history_changes_max:
            raise ValueError("a board fleet has no list, text or move "
                             "documents and no edits behind its load")
        spec = cls(n_boards=fleet.n_small, n_full=fleet.n_heavy,
                   history_cap=fleet.history_cap,
                   load_batch=fleet.load_batch, **config["boards"],
                   **config["writers"], **config["actions"])
        # a full board: its base change, then every device's cards
        spec.full_cards, rest = divmod(
            fleet.heavy_ops - (4 + 6 * spec.columns), 5 * spec.devices)
        if rest or spec.full_cards < 1:
            raise ValueError(f"heavy_ops {fleet.heavy_ops} is no full "
                             f"board's op count")
        if abs(spec.add_card + spec.mark_done + spec.reorder - 1) > 1e-9:
            raise ValueError("the actions' shares do not sum to 1")
        return spec


def actor_id(seed: int, board: str, k: int) -> str:
    """Device `k` of a board: 32 hex digits, as a uuid without its dashes;
    no two boards share one (the scheme of `fleets/devices.py`)."""
    import hashlib
    return hashlib.blake2b(f"{int(seed)}/{board}/{k}".encode(),
                           digest_size=16).hexdigest()


class Column:
    """One `cards` list as the fleet writes it: its object id, its element
    slots (tombstones count), the largest counter, the visible elements in
    the order they were made, and which elements lie under each anchor."""
    __slots__ = ("obj", "slots", "max_elem", "alive", "under")

    def __init__(self, obj: str):
        self.obj = obj
        self.slots = 0
        self.max_elem = 0
        self.alive: list = []
        self.under: dict = {}


class Board:
    """What the fleet keeps of one board to write its next change."""
    __slots__ = ("index", "devices", "seqs", "heads", "heads_before", "last",
                 "cols", "cards", "made", "depth", "before_max", "last_added",
                 "last_deleted", "last_ins")

    def __init__(self, index: int, devices: list):
        self.index = index
        self.devices = devices
        self.seqs = [0] * len(devices)
        self.heads: dict = {}
        self.heads_before: dict = {}
        self.last = -1
        self.cols: list = []
        self.cards: dict = {}      # (column, eid) -> [card, title, done, anchor]
        self.made = 0              # card maps made on this board
        self.depth = 0
        # the latest change, for a writer that has not seen it: the largest
        # counters before it, and the (column, eid) it added and deleted
        self.before_max: list = []
        self.last_added: tuple = ()
        self.last_deleted: tuple = ()
        self.last_ins: tuple = ()


class Fleet:
    dims_fixed = True
    # the schedule's `fields` draw is not read: what a change does is drawn
    # by the fleet's own generator, seeded by the request
    n_fields = 1

    def __init__(self, spec: Spec, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.small = [f"board{i:05d}" for i in range(spec.n_boards)]
        self.structured = [f"full{i:02d}" for i in range(spec.n_full)]
        self._id_prefix = f"{zlib.crc32(str(self.seed).encode()):08x}"
        self.boards: dict = {}
        self.anchored: set = set()
        self.duplicated: set = set()
        self.tombstoned: set = set()
        self.loaded = False
        self.first = {d: self._load_board(d, i + spec.n_boards,
                                          spec.full_cards, full=True)
                      for i, d in enumerate(self.structured)}

    @property
    def doc_ids(self) -> list:
        return self.structured + self.small

    def _obj(self, board: Board, kind: int) -> str:
        """A uuid-shaped object id: the fleet's seed, the board, a serial."""
        board.made += 1
        return (f"{self._id_prefix}-{kind:04x}-4{board.made & 0xFFF:03x}-"
                f"{0x8000 | (board.made >> 12):04x}-{board.index:012x}")

    # -- the load -----------------------------------------------------------

    def _load_board(self, d: str, index: int, n_cards: int,
                    full: bool = False) -> list:
        """A board's load: the base change by its first device, then one
        change a device, all concurrent after the base: `n_cards` cards each
        (into the first column on a full board, else a column drawn
        uniformly), one of them checked off."""
        spec = self.spec
        rng = random.Random(f"{self.seed}/{d}")
        b = Board(index, [actor_id(self.seed, d, k)
                          for k in range(spec.devices)])
        self.boards[d] = b
        a0 = b.devices[0]
        lists = self._obj(b, 1)
        ops = [Op("makeMap", board_obj := self._obj(b, 0)),
               Op("makeList", lists)]
        prev = HEAD
        for k in range(spec.columns):
            col, cards = self._obj(b, 2), self._obj(b, 3)
            ops += [Op("ins", lists, key=prev, elem=k + 1),
                    Op("makeMap", col),
                    Op("set", col, key="title", value=("todo", "done")[k]
                       if k < 2 else f"column {k}"),
                    Op("makeList", cards),
                    Op("link", col, key="cards", value=cards),
                    Op("link", lists, key=f"{a0}:{k + 1}", value=col)]
            prev = f"{a0}:{k + 1}"
            b.cols.append(Column(cards))
        ops += [Op("link", board_obj, key="lists", value=lists),
                Op("link", ROOT_ID, key="board", value=board_obj)]
        changes = [Change(a0, 1, {}, ops)]
        b.seqs[0] = 1
        b.depth = len(ops)
        heads = {}
        for w, actor in enumerate(b.devices):
            own = [[] for _ in b.cols]      # the device sees its own cards
            ops = []
            done = rng.randrange(n_cards)   # the card it checks off
            for j in range(n_cards):
                c = 0 if full else rng.randrange(spec.columns)
                k = rng.randrange(len(own[c]) + 1)
                anchor = own[c][k - 1] if k else HEAD
                own[c].append(self._insert(b, c, actor, len(own[c]) + 1,
                                           anchor, f"card {w}.{j}", j == done,
                                           ops))
            b.seqs[w] += 1
            changes.append(Change(actor, b.seqs[w],
                                  {a0: 1} if w else {}, ops))
            heads[actor] = b.seqs[w]
            b.depth += len(ops)
        # the latest change is the last device's; what a writer that has not
        # seen it knows is the board without that device's cards
        b.heads = heads
        b.heads_before = {a: s for a, s in heads.items()
                          if a != b.devices[-1]}
        b.last = len(b.devices) - 1
        b.last_added = frozenset(ce for ce in b.cards
                                 if ce[1].startswith(b.devices[-1] + ":"))
        b.before_max = [max((int(e.rsplit(":", 1)[1]) for e in col.alive
                             if (c, e) not in b.last_added), default=0)
                        for c, col in enumerate(b.cols)]
        return changes

    def load_rounds(self):
        """The load, one coalesced round at a time: the full boards first
        (they set the resident caps), then `load_batch` boards a round."""
        yield self.first
        yield from self.board_load_rounds()
        self.loaded = True

    def board_load_rounds(self):
        spec = self.spec
        for lo in range(0, spec.n_boards, spec.load_batch):
            yield {d: self._load_board(d, lo + i, spec.cards_at_load)
                   for i, d in enumerate(self.small[lo:lo + spec.load_batch])}

    # -- one change ---------------------------------------------------------

    def _insert(self, b: Board, c: int, actor: str, elem: int, anchor: str,
                title: str, done: bool, ops: list) -> str:
        """A card into column `c` after `anchor`: the five ops of an
        add-card, and the fleet's account of it."""
        col = b.cols[c]
        eid = f"{actor}:{elem}"
        card = self._obj(b, 4)
        ops += [Op("ins", col.obj, key=anchor, elem=elem),
                Op("makeMap", card),
                Op("set", card, key="title", value=title),
                Op("set", card, key="done", value=done),
                Op("link", col.obj, key=eid, value=card)]
        col.slots += 1
        col.max_elem = max(col.max_elem, elem)
        col.alive.append(eid)
        col.under.setdefault(anchor, []).append(eid)
        b.cards[(c, eid)] = [card, title, done, anchor]
        return eid

    def _write(self, d: str, u: list, title: str) -> Change:
        """The board's next change from its six uniform draws `u`
        (writer, concurrency, action, card, column, anchor)."""
        spec, b = self.spec, self.boards[d]
        cols = b.cols
        w = int(u[0] * len(b.devices))
        seen_all = not (b.last != w and u[1] < spec.concurrent_share)
        actor = b.devices[w]
        seq = b.seqs[w] = b.seqs[w] + 1
        if seen_all:
            seen = [col.alive for col in cols]
            top = [col.max_elem for col in cols]
        else:
            # the board as it was before its latest change
            hidden, revived = b.last_added, b.last_deleted
            seen = [[e for e in col.alive if (c, e) not in hidden]
                    + [e for c2, e in revived if c2 == c]
                    for c, col in enumerate(cols)]
            top = b.before_max
        b.before_max = [col.max_elem for col in cols]
        n_seen = sum(len(vis) for vis in seen)
        pick = u[2]
        if pick < spec.add_card or not n_seen:
            action = "add_card"
        elif pick < spec.add_card + spec.mark_done:
            action = "mark_done"
        else:
            action = "reorder"
        added = deleted = ins = ()
        ops: list = []
        if action == "mark_done":
            card = b.cards[_nth(seen, int(u[3] * n_seen))]
            card[2] = not card[2]
            ops.append(Op("set", card[0], key="done", value=card[2]))
        else:
            done = False
            if action == "reorder":
                src, eid = _nth(seen, int(u[3] * n_seen))
                _card, title, done, anchor = b.cards[(src, eid)]
                if not seen_all and (src, eid) in b.last_deleted:
                    self.duplicated.add(d)
                col = cols[src]
                ops.append(Op("del", col.obj, key=eid))
                if eid in col.alive:
                    col.alive.remove(eid)
                    deleted = ((src, eid),)
                if any(s != eid and s in col.alive
                       for s in col.under[anchor]):
                    self.tombstoned.add(d)
            c = int(u[4] * len(cols))
            vis = seen[c]
            if action == "reorder" and src == c and eid in vis:
                vis = [e for e in vis if e != eid]
            k = int(u[5] * (len(vis) + 1))
            anchor = vis[k - 1] if k else HEAD
            added = ((c, self._insert(b, c, actor, top[c] + 1, anchor,
                                      title, done, ops)),)
            ins = ((c, anchor),)
            if not seen_all and (c, anchor) in b.last_ins:
                self.anchored.add(d)
        before = b.heads if seen_all else b.heads_before
        deps = {a: s for a, s in before.items() if a != actor}
        if seen_all:
            heads = {actor: seq}
        else:
            latest = b.devices[b.last]
            heads = {latest: b.heads[latest], actor: seq}
        b.heads_before, b.heads = b.heads, heads
        b.last = w
        b.last_added, b.last_deleted, b.last_ins = added, deleted, ins
        b.depth += len(ops)
        return Change(actor, seq, deps, ops)

    # -- what run.py and the drivers ask of a fleet -------------------------

    def request_changes(self, drawn: tuple):
        """{board id: [Change]} of one request as the schedule drew it: one
        change a board, by one of its own devices; or `"history_cap"` /
        `"elem_cap"` where a board drawn could pass a cap."""
        docs, _fields, values = drawn
        spec, small, boards = self.spec, self.small, self.boards
        idx = docs.tolist()
        # an action adds at most 6 ops and one element to one list
        if any(boards[small[i]].depth + 6 > spec.history_cap for i in idx):
            return "history_cap"
        if any(col.slots + 1 > spec.elem_cap
               for i in idx for col in boards[small[i]].cols):
            return "elem_cap"
        u = np.random.default_rng(
            [self.seed, 0xB0A2D5, len(idx), int(docs.sum()),
             int(values.sum())]).random((len(idx), 6)).tolist()
        write = self._write
        return {small[i]: [write(small[i], ui, f"t{v}")]
                for i, v, ui in zip(idx, values.tolist(), u)}

    @staticmethod
    def request_ops(round_: dict) -> int:
        return sum(len(c.ops) for chs in round_.values() for c in chs)

    def replay(self, schedule, numbers) -> tuple:
        """Every acknowledged change made again from the seed on a fleet of
        its own: {board id: [Change]} of the load and the requests
        `numbers` in the order they were sent, and {(board id, actor, seq):
        request number} of the requests' changes."""
        again = type(self)(self.spec, self.seed)
        sent = {d: list(chs) for d, chs in again.first.items()}
        for round_ in again.board_load_rounds():
            sent.update(round_)
        again.loaded = True
        origin = {}
        for r in numbers:
            for d, chs in again.request_changes(schedule.request(r)).items():
                sent[d].extend(chs)
                origin[(d, chs[0].actor, chs[0].seq)] = r
        return sent, origin

    def load_line(self) -> dict:
        depths = sorted(self.boards[d].depth for d in self.small)
        slots = sorted(max(col.slots for col in self.boards[d].cols)
                       for d in self.small)
        return {"board_ops_min_median_max": [
                    depths[0], depths[len(depths) // 2], depths[-1]],
                "list_slots_min_median_max": [
                    slots[0], slots[len(slots) // 2], slots[-1]],
                "full_board_ops": [self.boards[d].depth
                                   for d in self.structured],
                "actor_ids": self.spec.devices * len(self.boards),
                "changes": sum(sum(b.seqs) for b in self.boards.values())}


def _nth(seen: list, k: int) -> tuple:
    """(column, eid) of the `k`-th element of the columns' visible lists,
    end to end."""
    for c, vis in enumerate(seen):
        if k < len(vis):
            return c, vis[k]
        k -= len(vis)
    raise IndexError(k)


def make(config: dict, seed: int) -> Fleet:
    return Fleet(Spec.from_config(config), seed)
