"""The fleet a configuration describes, made from a seed, and the service it
is loaded into. Copied from `chip_smoke.py` (PR 22, proven on the chip) so
that later changes to the smoke do not move the yardstick: `FleetSpec`,
`Fleet`, `make_fleet`, `apply_round`, the two service constructors and the
counter snapshot. Two additions: the edit history a small document is
loaded with (`history_changes_max`: a number of earlier edits drawn for
each document, uniform from none to that many), and `replay`, which makes
again, from the seed, every change the run sent, so that the run keeps
none of them while the window is timed.

This module is also the fleet kind a configuration gets by naming none:
`make(config, seed)` and the `Fleet` it returns are what `run.py` and the
drivers ask of any kind (`fleets/<name>.py`; README, "The three seams").
"""

from __future__ import annotations

import json
import os
import random
from dataclasses import dataclass, field, fields

HERE = os.path.dirname(os.path.abspath(__file__))

SMALL_KEYS = ("title", "n", "done", "f0", "f1", "f2", "f3")


def load_json(kind: str, name: str, root: str = HERE) -> dict:
    """`<root>/<kind>/<name>.json`: every configuration, mix, cell and
    metric is a file found by its name."""
    path = os.path.join(root, kind, name + ".json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class FleetSpec:
    history_changes_max: int         # a small document has 0..max seven-op
                                     # edits behind it, drawn uniformly
    history_cap: int                 # ops a document may hold before the
                                     # resident layout has to grow (README)
    n_small: int = 10_000
    n_heavy: int = 8
    heavy_ops: int = 400
    n_list: int = 16
    n_text: int = 16
    n_move: int = 4
    load_batch: int = 2_000          # small documents per load round

    @classmethod
    def from_config(cls, config: dict) -> "FleetSpec":
        known = {f.name for f in fields(cls)}
        given = config["fleet"]
        unknown = set(given) - known
        if unknown:
            raise ValueError(f"configuration fleet keys {sorted(unknown)} "
                             f"are not FleetSpec's {sorted(known)}")
        return cls(**given)


@dataclass
class Fleet:
    spec: FleetSpec
    small: list = field(default_factory=list)       # doc ids
    structured: list = field(default_factory=list)  # heavy/list/text/move
    seqs: dict = field(default_factory=dict)        # storm seq per doc
    depth: dict = field(default_factory=dict)       # ops sent per small doc
    first: dict = field(default_factory=dict)       # the structured
    # documents' load changes, kept: their object ids are not seeded
    seed: int = 0                                   # what `make` was given

    # growing a cap re-shapes the whole resident buffer (README): a run
    # whose resident dims moved inside the window ends with no result
    dims_fixed = True
    n_fields = len(SMALL_KEYS)     # fields a schedule may draw from

    @property
    def doc_ids(self) -> list:
        return self.structured + self.small

    # what run.py and the drivers ask of a fleet, whatever its kind

    def load_rounds(self):
        """The load, one coalesced round at a time, in order."""
        yield self.first
        yield from small_load_rounds(self, self.seed)

    def load_line(self) -> dict:
        """What the `load` stage prints about the fleet as loaded."""
        depths = sorted(self.depth.values())
        return {"small_depth_min_median_max": [
            depths[0], depths[len(depths) // 2], depths[-1]]}

    def request_changes(self, drawn: tuple):
        """{doc id: [Change]} of one request as the schedule drew it, or,
        where the fleet can take no more, the name of what stops it."""
        cap, depth, small = self.spec.history_cap, self.depth, self.small
        if any(depth[small[i]] >= cap for i in drawn[0].tolist()):
            return "history_cap"
        return request_changes(self, drawn)

    request_ops = staticmethod(len)    # ops of a request: one a document

    def replay(self, schedule, numbers) -> tuple:
        return replay(self, self.seed, schedule, numbers)


def make(config: dict, seed: int) -> Fleet:
    """The fleet of a configuration that names no kind of its own."""
    fleet = make_fleet(FleetSpec.from_config(config), seed)
    fleet.seed = int(seed)
    return fleet


def storm_change(fleet: Fleet, doc_id: str, ops: tuple) -> list:
    """One change by the actor `storm` with the document's next sequence
    number. `ops` is a tuple that changes may share: ops are only read."""
    from automerge_tpu.core.change import Change
    fleet.seqs[doc_id] = fleet.seqs.get(doc_id, 0) + 1
    fleet.depth[doc_id] = fleet.depth.get(doc_id, 0) + len(ops)
    return [Change("storm", fleet.seqs[doc_id], {}, ops)]


def _move_doc_base() -> list:
    """A small board: six maps under the root and one six-element list."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    ops = []
    for i in range(6):
        ops.append(Op("makeMap", f"f{i}"))
        ops.append(Op("link", ROOT_ID, key=f"k{i}", value=f"f{i}"))
    ops.append(Op("makeList", "L"))
    ops.append(Op("link", ROOT_ID, key="L", value="L"))
    prev = "_head"
    for e in range(1, 7):
        ops.append(Op("ins", "L", key=prev, elem=e))
        ops.append(Op("set", "L", key=f"A:{e}", value=f"v{e}"))
        prev = f"A:{e}"
    return [Change("A", 1, {}, ops)]


def make_fleet(spec: FleetSpec, seed: int) -> Fleet:
    """The seeded fleet with the load changes of its structured documents
    (`fleet.first`: they go in first and set the resident caps). The small
    map documents' load rounds come from `small_load_rounds`."""
    import automerge_tpu as am
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID

    rng = random.Random(seed)
    fleet = Fleet(spec)
    first: dict = {}
    for h in range(spec.n_heavy):
        first[f"heavy{h:02d}"] = [Change("storm", 1, {}, [
            Op("set", ROOT_ID, key=f"k{j}", value=rng.randrange(1 << 20))
            for j in range(spec.heavy_ops)])]
        fleet.seqs[f"heavy{h:02d}"] = 1
    for i in range(spec.n_list):
        a = am.change(am.init("A"), lambda d: d.__setitem__(
            "xs", [rng.randrange(100) for _ in range(12)]))
        b = am.merge(am.init("B"), a)
        a = am.change(a, lambda d: d["xs"].insert_at(rng.randrange(12), -1))
        b = am.change(b, lambda d: d["xs"].delete_at(rng.randrange(12)))
        a = am.merge(a, b)
        first[f"list{i:02d}"] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_text):
        a = am.change(am.init("A"), lambda d: d.__setitem__("t", am.Text()))
        a = am.change(a, lambda d: d["t"].insert_at(
            0, *(chr(97 + rng.randrange(26)) for _ in range(24))))
        b = am.merge(am.init("B"), a)
        a = am.change(a, lambda d: d["t"].insert_at(rng.randrange(24), "A"))
        b = am.change(b, lambda d: d["t"].insert_at(rng.randrange(24), *"bb"))
        b = am.change(b, lambda d: d["t"].delete_at(rng.randrange(20), 2))
        a = am.merge(a, b)
        first[f"text{i:02d}"] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_move):
        first[f"move{i:02d}"] = _move_doc_base() + [
            Change("B", 1, {"A": 1}, [Op("move", "f1", key="in", value="f0")]),
            Change("B", 2, {"B": 1}, [Op("move", "L", key="_head",
                                         value="A:4", elem=9)])]
    fleet.structured = list(first)
    fleet.first = first
    fleet.small = [f"doc{i:05d}" for i in range(spec.n_small)]
    return fleet


def small_load_rounds(fleet: Fleet, seed: int):
    """The small map documents' load rounds, `load_batch` documents each:
    a document's first three-op change and, behind it, 0..max seven-op
    edits (the number drawn for each document), all from a generator of
    their own, so that `replay` makes the same changes again."""
    from automerge_tpu.core.change import Op
    from automerge_tpu.core.ids import ROOT_ID

    spec = fleet.spec
    rng = random.Random(f"{int(seed)}/small")
    # the edits are drawn from a pool, so that a fleet is made in a second
    edits = [tuple(Op("set", ROOT_ID, key=k, value=rng.randrange(1 << 16))
                   for k in SMALL_KEYS) for _ in range(251)]
    for lo in range(0, spec.n_small, spec.load_batch):
        round_ = {}
        for d in fleet.small[lo:lo + spec.load_batch]:
            chs = storm_change(fleet, d, (
                Op("set", ROOT_ID, key="title", value=f"t{rng.randrange(999)}"),
                Op("set", ROOT_ID, key="n", value=rng.randrange(1 << 16)),
                Op("set", ROOT_ID, key="done", value=bool(rng.randrange(2)))))
            for _ in range(rng.randrange(spec.history_changes_max + 1)):
                chs += storm_change(fleet, d, edits[rng.randrange(251)])
            round_[d] = chs
        yield round_


def request_changes(fleet: Fleet, drawn: tuple) -> dict:
    """{doc id: [Change]} of one request as `Schedule.request` drew it: one
    change of one `set` op for each document, by the actor `storm`."""
    from automerge_tpu.core.change import Op
    from automerge_tpu.core.ids import ROOT_ID

    docs, fields, values = drawn
    small = fleet.small
    return {small[i]: storm_change(fleet, small[i], (
        Op("set", ROOT_ID, key=SMALL_KEYS[f], value=v),))
        for i, f, v in zip(docs.tolist(), fields.tolist(), values.tolist())}


def replay(fleet: Fleet, seed: int, schedule, numbers) -> tuple:
    """Every change the run sent and the service acknowledged, made again
    from the seed: {doc id: [Change]} of the structured documents' load
    (kept), the small documents' load and the requests `numbers` in the
    order they were sent; and {(doc id, seq): request number} of the
    requests' changes."""
    again = Fleet(fleet.spec, small=fleet.small, structured=fleet.structured)
    sent = {d: list(chs) for d, chs in fleet.first.items()}
    for round_ in small_load_rounds(again, seed):
        sent.update(round_)
    origin = {}
    for r in numbers:
        for d, chs in request_changes(again, schedule.request(r)).items():
            sent[d].extend(chs)
            origin[(d, chs[0].seq)] = r
    return sent, origin


def apply_round(svc, round_: dict) -> None:
    """One coalesced round through the service's own batching."""
    with svc.batch():
        for doc_id, changes in round_.items():
            svc.apply_changes(doc_id, changes)


def ops_ingested(svc) -> int:
    """Ops the service has flushed through its engine so far: the
    program's counter `sync_ops_ingested`, or the count the plain
    reference keeps when it stands in the program's place."""
    own = getattr(svc, "ops_ingested", None)
    if own is not None:
        return int(own)
    from automerge_tpu.utils import metrics
    return int(sum(v for k, v in metrics.snapshot().items()
                   if k.startswith("sync_ops_ingested")
                   and isinstance(v, int)))


def new_service(config: dict, devices):
    """The service a configuration names: `single` on the first device's
    default placement, `sharded` with one shard a device."""
    kind = config["service"]
    if kind == "single":
        from automerge_tpu.sync.service import EngineDocSet
        return EngineDocSet(backend="rows")
    if kind == "sharded":
        from automerge_tpu.sync.sharded_service import ShardedEngineDocSet
        n = config["n_shards"]
        if len(devices) < n:
            raise RuntimeError(f"{n} shards need {n} devices, "
                               f"got {len(devices)}")
        return ShardedEngineDocSet(n_shards=n, devices=list(devices)[:n])
    raise ValueError(f"service kind {kind!r} is not single or sharded")


def engines(svc) -> list:
    """The resident engines of a service; none for the plain reference
    put in its place."""
    shards = getattr(svc, "shards", None)
    if shards:
        return [s._resident for s in shards]
    one = getattr(svc, "_resident", None)
    return [one] if one is not None else []


def resident_dims(svc) -> list:
    return [list(e.dims()) + [e.n_pad] for e in engines(svc)]


def counters() -> dict:
    """The program's counters, histograms, phases and per-kernel compile
    counts as one flat {name: number}: labelled series are summed over
    their labels under the bare name, phases appear as `phase.<name>`,
    compiles as `compiles.<kernel>` and `compile_s.<kernel>`."""
    from automerge_tpu.utils import metrics
    snap = metrics.snapshot()
    out: dict = {}
    for k, v in snap.items():
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            continue
        if "{" in k:
            bare = k[:k.index("{")] + k[k.index("}") + 1:]
            if bare.endswith(("_min", "_max")):
                continue
            out[bare] = out.get(bare, 0) + v
            out[k] = v
        else:
            out[k] = out.get(k, 0) + v
    perf = snap.get("perf") or {}
    for name, row in (perf.get("phases") or {}).items():
        out[f"phase.{name}"] = row["s"]
    for name, row in (perf.get("kernels") or {}).items():
        out[f"compiles.{name}"] = row["compiles"]
        out[f"compile_s.{name}"] = row["compile_s"]
        out[f"dispatches.{name}"] = row["dispatches"]
    return out


def counter_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if not k.endswith(("_min", "_max"))}
