"""The plain reference of the fleet configurations: Automerge's map
semantics and the canonical state hash, in straightforward Python.

It imports nothing of the program and takes nothing the program has made:
it reads the changes the benchmark itself sent (`.actor`, `.seq`, `.deps`,
`.ops[*].action/.obj/.key/.value`, by attribute) and follows the published
semantics:

- a change is applied once its dependencies are (`deps` plus the actor's
  previous sequence number);
- an assignment (`set`, `del`) to a key removes every earlier assignment to
  that key that its change had seen (causally before it), and `set` then
  stands beside the ones it had not seen (concurrent);
- the winner of a key is the surviving `set` of the highest actor id; the
  others are its conflicts.

The state hash is the one the product documents as its convergence oracle
(`automerge_tpu/engine/kernels.py` `state_hash`, `engine/encode.py`
`content_hash` / `value_bytes`): the sum, modulo 2**32, over every surviving
`set` of `mix4(-7, crc32(object "\\0" key), crc32(actor), crc32(value
bytes))`, each crc cut to 31 bits. It is written out again here so that a
later change to the program's kernels or encoders is read against the same
definition.

Only documents whose every op is a `set` or `del` on the root map are
covered (`covers`): the fleet's 10,000 small and 8 heavy documents, which
take all the traffic. The list, text and move documents are held to
"unchanged by the window" by `check.py`; a plain RGA and move reference is
an Open question in PERF.md.

`RefService` is this reference put in the program's place: a doc set with
the service's five calls. Its `broken` argument breaks one guarantee of
the configuration; that is the control of `correct` (PERF.md section 2).
"""

from __future__ import annotations

import contextlib
import struct
import zlib

ROOT_ID = "00000000-0000-0000-0000-000000000000"
_M32 = 0xFFFFFFFF


def covers(changes) -> bool:
    return all(op.action in ("set", "del") and op.obj == ROOT_ID
               for c in changes for op in c.ops)


def causal_order(changes) -> list:
    """The changes, deduplicated, in an order in which each follows what it
    depends on; a change whose dependencies never arrive is left out."""
    by_id = {}
    for c in changes:
        by_id.setdefault((c.actor, c.seq), c)
    queued = sorted(by_id.values(), key=lambda c: (c.actor, c.seq))
    clock: dict = {}
    ready = []
    progress = True
    while progress and queued:
        progress = False
        still = []
        for c in queued:
            deps = dict(c.deps)
            deps[c.actor] = c.seq - 1
            if all(clock.get(a, 0) >= s for a, s in deps.items()):
                ready.append(c)
                clock[c.actor] = max(clock.get(c.actor, 0), c.seq)
                progress = True
            else:
                still.append(c)
        queued = still
    return ready


def survivors(changes) -> dict:
    """{key: [(actor, seq, value)]} of the `set` ops that survive."""
    seen: dict = {}      # (actor, seq) -> everything that change had seen
    fields: dict = {}    # key -> [(actor, seq, action, value)]
    for c in causal_order(changes):
        saw: dict = {}
        base = dict(c.deps)
        base[c.actor] = c.seq - 1
        for a, s in base.items():
            if s <= 0:
                continue
            for a2, s2 in seen.get((a, s), {}).items():
                if s2 > saw.get(a2, 0):
                    saw[a2] = s2
            if s > saw.get(a, 0):
                saw[a] = s
        seen[(c.actor, c.seq)] = saw
        for op in c.ops:
            kept = [o for o in fields.get(op.key, ())
                    if saw.get(o[0], 0) < o[1]]
            kept.append((c.actor, c.seq, op.action, op.value))
            fields[op.key] = kept
    out = {}
    for key, ops in fields.items():
        sets = [(a, s, v) for a, s, action, v in ops if action == "set"]
        if sets:
            out[key] = sets
    return out


def state(changes) -> dict:
    """{"data", "conflicts"} as the service's `materialize` renders a map
    document."""
    data, conflicts = {}, {}
    for key, sets in survivors(changes).items():
        sets = sorted(sets, key=lambda o: o[0], reverse=True)
        data[key] = sets[0][2]
        if len(sets) > 1:
            conflicts[key] = {a: v for a, _s, v in sets[1:]}
    return {"data": data, "conflicts": conflicts}


def _crc31(raw: bytes) -> int:
    return zlib.crc32(raw) & 0x7FFFFFFF


def value_bytes(value) -> bytes:
    if value is None:
        return b"n"
    if value is True:
        return b"b:1"
    if value is False:
        return b"b:0"
    if isinstance(value, int):
        return b"i:%d" % value
    if isinstance(value, float):
        return b"d:" + struct.pack("<d", value)
    if isinstance(value, str):
        return b"s:" + value.encode("utf-8", "surrogatepass")
    raise TypeError(f"no canonical bytes for {type(value).__name__}")


def _mix(h: int) -> int:
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    h ^= h >> 16
    return h


def _mix4(a: int, b: int, c: int, d: int) -> int:
    h = _mix((a + 0x9E3779B9) & _M32)
    h = _mix(h ^ b)
    h = _mix(h ^ c)
    return _mix(h ^ d)


_MAP_FIELD = -7 & _M32
_memo: dict = {}


def _memo_crc(kind: str, text: str) -> int:
    h = _memo.get((kind, text))
    if h is None:
        h = _crc31(text.encode("utf-8", "surrogatepass"))
        if len(_memo) < 100_000:
            _memo[(kind, text)] = h
    return h


def _set_hash(key: str, actor: str, value) -> int:
    """What one surviving `set` on the root map adds to the state hash."""
    return _mix4(_MAP_FIELD, _memo_crc("f", f"{ROOT_ID}\x00{key}"),
                 _memo_crc("a", actor), _crc31(value_bytes(value)))


def state_hash(changes) -> int:
    return sum(_set_hash(key, actor, value)
               for key, sets in survivors(changes).items()
               for actor, _seq, value in sets) & _M32


# ---------------------------------------------------------------------------
# the reference in the program's place


BROKEN = ("none", "ack_before_flush", "lose_acknowledged", "stale_hash",
          "first_writer_wins")


class RefService:
    """A doc set with the service's calls, on the plain reference. `broken`
    names the guarantee of the configuration that this instance breaks:

    - `ack_before_flush`: the acknowledging call returns at once and the
      flush waits for the next read: every answer is right in the end, and
      every acknowledgement came before its change was flushed;
    - `lose_acknowledged`: one acknowledged change in `every` is dropped
      from the log, so `missing_changes` does not serve it back;
    - `stale_hash`: one flush in seven stops refreshing the hash of one of
      its dirty documents, for good (a flush that holds no document with
      a hash yet, as a load round of new documents does, is passed over);
    - `first_writer_wins`: a `set` never removes an earlier one, so the
      materialized state and the hash are no longer Automerge's.
    """

    def __init__(self, broken: str = "none", every: int = 97):
        if broken not in BROKEN:
            raise ValueError(f"broken={broken!r}, not one of {BROKEN}")
        self.broken, self.every = broken, every
        self.logs: dict = {}
        self._hashes: dict = {}
        self._dirty: set = set()
        self._stale: set = set()
        self._depth = 0
        self._n_acked = self._n_flushes = 0
        self._pending_ops = 0
        self.ops_ingested = 0     # ops flushed so far (fleet.ops_ingested)

    @property
    def doc_ids(self) -> list:
        return list(self.logs)

    def batch(self):
        @contextlib.contextmanager
        def _cm():
            self._depth += 1
            try:
                yield self
            finally:
                self._depth -= 1
                if not self._depth and self.broken != "ack_before_flush":
                    self._flush()
        return _cm()

    def apply_changes(self, doc_id: str, changes) -> None:
        log = self.logs.setdefault(doc_id, [])
        for c in changes:
            self._n_acked += 1
            if self.broken == "lose_acknowledged" \
                    and self._n_acked % self.every == 0:
                continue
            log.append(c)
            self._pending_ops += len(c.ops)
        self._dirty.add(doc_id)
        if not self._depth and self.broken != "ack_before_flush":
            self._flush()

    def _flush(self) -> None:
        if not self._dirty:
            return
        self.ops_ingested += self._pending_ops
        self._pending_ops = 0
        self._n_flushes += 1
        if self.broken == "stale_hash" and self._n_flushes % 7 == 0:
            hashed = [d for d in self._dirty if d in self._hashes]
            if hashed:
                self._stale.add(min(hashed))
        for d in self._dirty - self._stale:
            self._hashes[d] = self._hash_of(d)
        self._dirty.clear()

    def _hash_of(self, doc_id: str) -> int:
        log = self.logs[doc_id]
        if not covers(log):
            return _crc31(repr([(c.actor, c.seq) for c in log]).encode())
        if self.broken == "first_writer_wins":
            return _first_writer_hash(log)
        return state_hash(log)

    def missing_changes(self, doc_id: str, clock: dict) -> list:
        self._flush()
        return [c for c in self.logs.get(doc_id, ())
                if c.seq > clock.get(c.actor, 0)]

    def hashes(self) -> dict:
        self._flush()
        return dict(self._hashes)

    def materialize(self, doc_id: str):
        self._flush()
        log = self.logs[doc_id]
        if not covers(log):
            return {"data": {"opaque": len(log)}, "conflicts": {}}
        if self.broken == "first_writer_wins":
            return _first_writer_state(log)
        return state(log)

    def close(self) -> None:
        pass


def _first_writer(changes) -> dict:
    first: dict = {}
    for c in causal_order(changes):
        for op in c.ops:
            if op.action == "set":
                first.setdefault(op.key, (c.actor, c.seq, op.value))
    return first


def _first_writer_state(changes) -> dict:
    return {"data": {k: v for k, (_a, _s, v) in _first_writer(changes)
                     .items()}, "conflicts": {}}


def _first_writer_hash(changes) -> int:
    return sum(_set_hash(key, actor, value) for key, (actor, _seq, value)
               in _first_writer(changes).items()) & _M32
