"""Every kernel family compiled for a described TPU v5e, without the chip.

The suite runs on the CPU, where the Pallas kernels run in the interpreter;
what the chip's own compiler refuses (a block shape off the (8, 128) tiling,
a working set past the scoped VMEM) no interpret-mode test can see. The TPU
compiler is installed here and compiles for a chip that is described and not
attached, so each program of the served path is lowered and compiled at the
shapes the 10,000-document chip_smoke.py produces (its CPU rehearsal printed
them: resident dims (512, 4, 64) over 10,112 lanes, storm buckets (8, 4, 0)
over 1,024 and 2,048 lanes, span and move tables [8, ., 128]).

The cells of the benchmark are here at their own shapes too. The actor axis
is a document's own, so `fleet10k`, no document of which has more than two
writers, loads at resident dims (512, 2, 32): `reconcile_rows_hash` over
[5282, 1280]; `_apply_final` on the [5282, 10112] fleet, reconciling the one
128-lane block a request dirtied, and every block as the first request
after an upload does. `fleet10k-devices`, whose heavy documents have eight
writers, loads at (512, 8, 32), a working set of 22,248 of the budget's
22,528 rows: `reconcile_rows_hash` over [8360, 1280], `_apply_final` on the
[8360, 10112] fleet, and the round's scatter and gather at that height.
`boards10k`, eight devices a board and three lists of 128 element slots,
loads at (512, 8, 512), past the standard kernel's budget: the XL variant
over [10760, 1280] and the round's scatter into [10760, 10112]. `lists10k`,
four devices and two lists of 256 slots a document, loads at (512, 4, 512):
the XL variant over [8708, 1280], the round's scatter and gather, and the
in-place put of the lanes a round compacted. And
each compiled program must hold an instruction that the cell's roofline
metric finds by the patterns of its own file under benchmarks/metrics/: a
renamed kernel then fails here, and not as `output_malformed` on the chip.
So are the shapes of a shard of the four-chip cell, `fleet10k-4shard.storm`
(a quarter of the fleet a chip, the same caps): `reconcile_rows_hash` over
the [5282, 384] a quarter of a storm round pads to, and over 256 and 512
lanes, the neighbours a seed may reach; `_apply_final` on a shard's
resident [5282, 2560] at one block.

Since a round keeps the rows on the chip, its two programs are here as
well: `_scatter_trips` at the power-of-two triplet pads a round of either
cell reaches, and `gather_lanes` out of the fleet's [5282, 10112] (a
shard's [5282, 2560]) into the 1152, 1280 and 1408 (256, 384, 512) lanes
a round's dirty documents pad to. The gather may need no temporary worth
the name: `rows[:, sel]` as XLA lowers it copies the whole buffer into
another layout first, 255 MB a request. The scatter is XLA's and does
re-lay the donated buffer on the device around its updates (3.1 ms a
round of 12,300 triplets on the chip); it is held to the device's memory
alone. The reconcile that follows the gather is `reconcile_rows_hash` on
those same lane counts, each held to its roofline metric's patterns.

Nothing runs and no time is implied: a compile that passes is not a chip
run. The topology is described inside a fixture, never at import (only one
process at a time may load the TPU's library, and every xdist worker imports
this file), and all cases live in this one file so one worker owns the
library.
"""

import json
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from automerge_tpu.engine.pack import rows_count

HBM_BYTES = 16 * 1024 ** 3     # one v5e chip

FLEET_LANES = 10_112           # pad_to_lanes(10,044 documents)
CAPS = (512, 4, 64)            # the smoke's resident (I, A, LE)
BENCH_CAPS = (512, 2, 32)      # fleet10k's (its load stage line)
DEVICES_CAPS = (512, 8, 32)    # fleet10k-devices': eight writers a heavy doc
# boards10k's: eight devices a board, three lists of 128 element slots (the
# list axis pads to four): past the standard kernel's budget, the XL variant
BOARDS_CAPS = (512, 8, 512)
# lists10k's: four devices a list, two lists of 256 element slots a
# document: the XL variant over [8708, .]
LISTS_CAPS = (512, 4, 512)
BENCH_STORM_LANES = 1_280      # a storm request's dirty documents, padded
SHARD_LANES = 2_560            # pad_to_lanes(a shard's 2,510 or 2,512 documents)
SHARD_STORM_LANES = (256, 384, 512)   # a quarter of a round: 262-354 documents
STORM_LANES = (1_152, 1_280, 1_408)   # a round: 1,170-1,300 documents
# a round's merged triplets (9-11 a change), padded to a power of two
STORM_TRIP_PADS = (8_192, 16_384)
SHARD_TRIP_PADS = (2_048, 4_096)
# fleet10k-devices: a change names its deps' clock in more bands, and a
# join's lane rewrite rides the round's scatter
DEVICES_TRIP_PADS = (16_384, 32_768)
# boards10k: a change is 3.6 ops in the mean and an insert rewrites the
# positions of its whole list: a round's scatter sorts s32[131072] on the
# chip, and a smaller round pads to 65,536
BOARDS_TRIP_PADS = (65_536, 131_072)
# lists10k: 3.6 ops a change, and the positions of some 900 lists of
# 100-180 slots a round
LISTS_TRIP_PADS = (131_072, 262_144)


def _dims(i, a, le):
    from automerge_tpu.engine.encode import A_DEL, A_SET
    return (i, a, le, int(A_SET), int(A_DEL))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


class Chip:
    """Shapes placed on the described chip: `one(shape)` on a single
    device, `meshed(shape, spec)` over the four-device mesh."""

    def __init__(self, topo):
        self._one = SingleDeviceSharding(topo.devices[0])
        self.mesh = Mesh(topo.devices, ("docs",))

    def one(self, shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._one)

    def meshed(self, shape, spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(self.mesh, spec))


@pytest.fixture(scope="module")
def chip(topo):
    return Chip(topo)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _megakernel(i, a, le, lanes, force_xl=False):
    def build(chip):
        from automerge_tpu.engine.pallas_kernels import reconcile_rows_hash
        return reconcile_rows_hash.lower(
            chip.one((rows_count(i, a, le), lanes)), _dims(i, a, le), False,
            force_xl=force_xl)
    return build


def _apply_final(caps, trips, blocks=None, lanes=FLEET_LANES):
    """`_apply_final` on the fleet (or a shard's `lanes` of it) at
    resident `caps`: reconciling `blocks` 128-lane blocks and patching
    their hashes into the last hash vector, or with None every block, as
    after an upload."""
    def build(chip):
        from automerge_tpu.engine.resident_rows import _apply_final
        by_block = (None, None) if blocks is None else (
            chip.one((blocks,)), chip.one((lanes,), jnp.uint32))
        return _apply_final.lower(
            chip.one((rows_count(*caps), lanes)),
            chip.one((trips, 3)), *by_block, _dims(*caps), False)
    return build


def _scatter_trips(lanes, trips, caps=BENCH_CAPS):
    def build(chip):
        from automerge_tpu.engine.resident_rows import _scatter_trips
        return _scatter_trips.lower(
            chip.one((rows_count(*caps), lanes)), chip.one((trips, 3)))
    return build


def _gather_lanes(lanes, k_pad, caps=BENCH_CAPS):
    def build(chip):
        from automerge_tpu.engine.pallas_kernels import gather_lanes
        steps = lanes // 128 + k_pad // 128
        return gather_lanes.lower(
            chip.one((rows_count(*caps), lanes)),
            chip.one((k_pad + 2 * steps,)), k_pad, False)
    return build


def _put_cols(lanes, caps):
    """A compaction's put of 32 rewritten lanes into the resident rows."""
    def build(chip):
        from automerge_tpu.engine.resident_rows import LANE_PUT, _put_cols
        rows = rows_count(*caps)
        return _put_cols.lower(chip.one((rows, lanes)),
                               chip.one((rows, LANE_PUT)),
                               chip.one((LANE_PUT,)))
    return build


def _scan_rounds_fleet(chip):
    from automerge_tpu.engine.resident_rows import _scan_rounds
    return _scan_rounds.lower(
        chip.one((rows_count(*CAPS), FLEET_LANES)), chip.one((2, 1024, 3)),
        _dims(*CAPS), False)


def _merge_spans(chip):
    from automerge_tpu.engine.span_kernels import merge_spans
    return merge_spans.lower(chip.one((8, 8, 128)))


def _span_rank_hash_pallas(chip):
    from automerge_tpu.engine.span_kernels import span_rank_hash_pallas
    return span_rank_hash_pallas.lower(chip.one((8, 8, 256)),
                                       interpret=False)


def _resolve_moves(chip):
    from automerge_tpu.engine.move_kernels import resolve_moves
    return resolve_moves.lower(chip.one((8, 4, 128)), chip.one((8, 3, 128)))


def _move_round_pallas(chip):
    from automerge_tpu.engine.move_kernels import move_round_pallas
    return move_round_pallas.lower(
        chip.one((8, 4, 128)), chip.one((8, 3, 128)), chip.one((8, 128)),
        interpret=False)


def _dominated_pallas(chip):
    from automerge_tpu.engine.pallas_kernels import dominated_pallas
    row = chip.one((32, 512))
    return dominated_pallas.lower(
        chip.one((32, 512, 8)), row, row, row, row,
        chip.one((32, 512), jnp.bool_), interpret=False)


def _apply_doc_reference(chip):
    """The XLA reference of chip_smoke's parity stage, one chunk of small
    documents (a 10,000-document batch compiles too, in 20 s)."""
    import __graft_entry__ as graft
    from automerge_tpu.engine.kernels import apply_doc
    batch, max_fids = graft._example_batch(2)
    shapes = {k: chip.one((1024,) + v.shape[1:], v.dtype)
              for k, v in batch.items()}
    return jax.jit(lambda b: apply_doc(b, max_fids, host_order=True)
                   ).lower(shapes)


def _sharded_megakernel(chip):
    from automerge_tpu.parallel.mesh import DOCS_AXIS, _sharded_rows_fn
    fn = _sharded_rows_fn(chip.mesh, _dims(*CAPS), False)
    return fn.lower(chip.meshed((rows_count(*CAPS), 4 * 256),
                                P(None, DOCS_AXIS)))


# name -> (builder, whether the program holds a Pallas kernel[, the most
# bytes of temporaries the compiled program may need])
CASES = {
    "megakernel-base-storm-bucket": (_megakernel(8, 4, 0, 2048), True),
    "megakernel-base-caps-one-block": (_megakernel(*CAPS, 128), True),
    # refused before this file existed: two grid steps double-buffer the
    # input block past the default scoped VMEM
    "megakernel-base-caps-fleet": (_megakernel(*CAPS, FLEET_LANES), True),
    "megakernel-xl": (_megakernel(512, 8, 128, 256), True),
    "megakernel-xl-forced": (
        _megakernel(1024, 8, 512, 128, force_xl=True), True),
    "apply_final-fleet": (_apply_final(CAPS, 1024), True),
    # a block route that copies the 255 MB buffer (reshape + take does)
    # fails here and not as 3 ms a request on the chip
    "apply_final-bench-one-block": (
        _apply_final(BENCH_CAPS, 16, blocks=1), True, 1 << 20),
    "apply_final-bench-four-blocks": (
        _apply_final(BENCH_CAPS, 16, blocks=4), True, 1 << 20),
    # eight writers a document: 22,248 of the budget's 22,528 rows
    "megakernel-devices-one-block": (_megakernel(*DEVICES_CAPS, 128), True),
    # a round of fleet10k-devices.storm: ten blocks, each bounded by the
    # live extents the wrapper reduces out of the rows (an SMEM operand)
    "megakernel-devices-storm": (
        _megakernel(*DEVICES_CAPS, BENCH_STORM_LANES), True),
    "apply_final-devices-one-block": (
        _apply_final(DEVICES_CAPS, 16, blocks=1), True, 1 << 20),
    "apply_final-devices-whole": (_apply_final(DEVICES_CAPS, 1024), True),
    # boards10k.storm: a round's gathered lanes through the XL variant
    "megakernel-boards-xl-storm": (
        _megakernel(*BOARDS_CAPS, BENCH_STORM_LANES), True),
    # lists10k.storm: a round's gathered lanes through the XL variant, and
    # the put of a round's compacted lanes, written in place (a put that
    # copies the buffer fails on its temporaries)
    "megakernel-lists-xl-storm": (
        _megakernel(*LISTS_CAPS, BENCH_STORM_LANES), True),
    "put_lanes-lists": (_put_cols(FLEET_LANES, LISTS_CAPS), False, 1 << 20),
    "scan_rounds-fleet": (_scan_rounds_fleet, True),
    "merge_spans": (_merge_spans, False),
    "resolve_moves": (_resolve_moves, False),
    # refused at D > 1 before their blocks squeezed the docs axis
    "span_rank_hash_pallas-8-docs": (_span_rank_hash_pallas, True),
    "move_round_pallas-8-docs": (_move_round_pallas, True),
    "dominated_pallas": (_dominated_pallas, True),
    "apply_doc-reference": (_apply_doc_reference, False),
    "sharded-megakernel-4-devices": (_sharded_megakernel, True),
}
CASES.update({
    f"scatter_trips-{name}-{trips}-triplets": (
        _scatter_trips(lanes, trips, caps), False)
    for name, lanes, pads, caps in (
        ("fleet", FLEET_LANES, STORM_TRIP_PADS, BENCH_CAPS),
        ("shard", SHARD_LANES, SHARD_TRIP_PADS, BENCH_CAPS),
        ("devices", FLEET_LANES, DEVICES_TRIP_PADS, DEVICES_CAPS),
        ("boards", FLEET_LANES, BOARDS_TRIP_PADS, BOARDS_CAPS),
        ("lists", FLEET_LANES, LISTS_TRIP_PADS, LISTS_CAPS))
    for trips in pads})
# a gather that copies the buffer it reads (XLA's `rows[:, sel]` does)
# fails here: its only large buffer is its output
CASES.update({
    f"gather_lanes-{name}-{k_pad}-lanes": (
        _gather_lanes(lanes, k_pad, caps), True, 1 << 20)
    for name, lanes, pads, caps in (
        ("fleet", FLEET_LANES, STORM_LANES, BENCH_CAPS),
        ("shard", SHARD_LANES, SHARD_STORM_LANES, BENCH_CAPS),
        ("devices", FLEET_LANES, STORM_LANES, DEVICES_CAPS),
        ("boards", FLEET_LANES, STORM_LANES, BOARDS_CAPS),
        ("lists", FLEET_LANES, STORM_LANES, LISTS_CAPS))
    for k_pad in pads})


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, chip, no_compile_cache):
    build, has_kernel, *temp_limit = CASES[case]
    compiled = build(chip).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{case}: {used} bytes on one device"
    for limit in temp_limit:
        assert mem.temp_size_in_bytes < limit, (
            f"{case}: {mem.temp_size_in_bytes} bytes of temporaries")
    assert ("tpu_custom_call" in compiled.as_text()) == has_kernel, (
        f"{case}: Pallas kernel in the compiled program: {not has_kernel}")


# case -> (roofline metric, builder of its cell's kernel call, the shape
# the metric's `shape` pattern must read from the instruction)
CELL_KERNELS = {
    "megakernel_roofline": (
        "megakernel_roofline", _megakernel(*BENCH_CAPS, BENCH_STORM_LANES),
        (rows_count(*BENCH_CAPS), BENCH_STORM_LANES)),
    # a request of the `edits` cell: the one block it dirtied
    "apply_final_roofline": (
        "apply_final_roofline", _apply_final(BENCH_CAPS, 16, blocks=1),
        (rows_count(*BENCH_CAPS), 128)),
    # the first request after an upload: every block
    "apply_final_roofline-after-upload": (
        "apply_final_roofline", _apply_final(BENCH_CAPS, 16),
        (rows_count(*BENCH_CAPS), FLEET_LANES)),
    # a single edit on a shard of the four-chip cell: one block of its
    # resident [5282, 2560]
    "apply_final_roofline-shard-one-block": (
        "apply_final_roofline",
        _apply_final(BENCH_CAPS, 16, blocks=1, lanes=SHARD_LANES),
        (rows_count(*BENCH_CAPS), 128)),
}
# a shard's quarter of a storm round
CELL_KERNELS.update({
    f"megakernel_roofline-shard-{lanes}-lanes": (
        "megakernel_roofline", _megakernel(*BENCH_CAPS, lanes),
        (rows_count(*BENCH_CAPS), lanes))
    for lanes in SHARD_STORM_LANES})
# the reconcile behind a round's device gather: the same top-level jit on
# the gathered lanes, at the neighbours of 1,280 a seed may reach
CELL_KERNELS.update({
    f"megakernel_roofline-{lanes}-lanes": (
        "megakernel_roofline", _megakernel(*BENCH_CAPS, lanes),
        (rows_count(*BENCH_CAPS), lanes))
    for lanes in STORM_LANES if lanes != BENCH_STORM_LANES})
# fleet10k-devices.storm: the same round at eight writers a document
CELL_KERNELS.update({
    f"megakernel_roofline-devices-{lanes}-lanes": (
        "megakernel_roofline", _megakernel(*DEVICES_CAPS, lanes),
        (rows_count(*DEVICES_CAPS), lanes))
    for lanes in STORM_LANES})
# boards10k.storm: the XL variant under the same instruction name
CELL_KERNELS.update({
    f"megakernel_roofline-boards-{lanes}-lanes": (
        "megakernel_roofline", _megakernel(*BOARDS_CAPS, lanes),
        (rows_count(*BOARDS_CAPS), lanes))
    for lanes in STORM_LANES})


def _event_names(compiled) -> list:
    """The instructions of a compiled program as the profiler names their
    events on the device's operations line: the HLO text with operand
    shapes, one instruction a line."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()]


@pytest.mark.parametrize("case", sorted(CELL_KERNELS))
def test_cell_kernel_is_found_by_its_roofline_metric(case, chip,
                                                     no_compile_cache):
    metric, build, want = CELL_KERNELS[case]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "metrics", metric + ".json"),
              encoding="utf-8") as f:
        args = json.load(f)["args"]
    event, shape = re.compile(args["event"]), re.compile(args["shape"])
    compiled = build(chip).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    hits = [ln for ln in _event_names(compiled) if event.search(ln)]
    assert hits, f"{metric}: no instruction matches {args['event']!r}"
    read = [tuple(map(int, m.groups()))
            for m in map(shape.search, hits) if m]
    assert want in read, (
        f"{metric}: {args['shape']!r} reads {read} from {hits[0][:200]!r}")
