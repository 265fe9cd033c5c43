"""Device-mesh sharding of batched DocSet reconciliation.

The reference's unit of distribution is the DocSet synced per-connection
(/root/reference/src/connection.js); its only parallelism is replica
parallelism across network peers (SURVEY.md §2.3). The TPU-native equivalent:
the document axis of a columnar batch is sharded across a
`jax.sharding.Mesh`, and one jitted program reconciles the whole set with XLA
inserting any needed collectives. Documents are independent, so the forward
pass is embarrassingly parallel over ICI; cross-document reductions (global
clock unions, convergence checks) become mesh collectives
(parallel/collective.py).

On a multi-host pod the same code runs under jax.distributed with a global
mesh; the host boundary still speaks the reference's {docId, clock, changes}
schema over DCN while device shards reconcile in parallel.
"""

from __future__ import annotations

import numpy as np

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.encode import encode_doc, stack_docs

DOCS_AXIS = "docs"


def make_mesh(n_devices: int | None = None, axis: str = DOCS_AXIS) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def _pad_docs(batch: dict, multiple: int) -> dict:
    """Pad the leading docs axis so it divides the mesh size; padded docs are
    fully masked out and contribute nothing."""
    n_docs = batch["op_mask"].shape[0]
    rem = n_docs % multiple
    if rem == 0:
        return batch
    pad = multiple - rem
    out = {}
    for key, arr in batch.items():
        if not isinstance(arr, np.ndarray):
            out[key] = arr
            continue
        widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        fill = False if arr.dtype == bool else (0 if key in ("actor", "seq", "change_idx", "clock", "ins_elem", "ins_actor") else -1)
        out[key] = np.pad(arr, widths, constant_values=fill)
    return out


def shard_batch(batch: dict, mesh: Mesh):
    """device_put every batch array with the docs axis sharded over the mesh."""
    sharding = NamedSharding(mesh, P(DOCS_AXIS))
    return {k: jax.device_put(np.asarray(v), sharding) for k, v in batch.items()}


_SHARDED_APPLY_CACHE: dict = {}


def sharded_apply(arrays: dict, max_fids: int, mesh: Mesh):
    """The batched reconcile kernel jitted over the mesh: inputs arrive
    sharded over docs, outputs stay sharded over docs. The jitted wrapper
    is cached per (mesh, max_fids) — a fresh jax.jit per call would drop
    its compile cache on the floor and retrace every time (the graftlint
    jit-retrace rule; the rows/bytes builders below always cached)."""
    from ..engine.kernels import apply_doc
    key = (mesh, max_fids)
    fn = _SHARDED_APPLY_CACHE.get(key)
    if fn is None:
        out_sharding = NamedSharding(mesh, P(DOCS_AXIS))
        fn = jax.jit(lambda b: apply_doc(b, max_fids, host_order=True),
                     out_shardings=out_sharding)
        _SHARDED_APPLY_CACHE[key] = fn
    return fn(arrays)


def encode_padded_batch(doc_changes, mesh: Mesh, multiple: int | None = None):
    """Encode per-document change sets into a stacked batch padded to the
    mesh size (or an explicit `multiple`, e.g. 128 * mesh size for lane-
    sharded kernels). Deterministic given the change sets alone (sorted
    global actor order), so every host of a multi-host run produces a
    bit-identical description — the precondition for contributing local
    shards of one global array (parallel/multihost.py)."""
    all_actors = sorted({c.actor for changes in doc_changes for c in changes})
    encodings = [encode_doc(changes, all_actors) for changes in doc_changes]
    batch = stack_docs(encodings)
    max_fids = batch.pop("max_fids")
    return (encodings,
            _pad_docs(batch, multiple or mesh.devices.size), max_fids)


def reconcile_sharded(doc_changes, mesh: Mesh):
    """End-to-end: encode a list of per-document change sets, shard them over
    the mesh, reconcile, and return (encodings, sharded outputs, n_real_docs)."""
    encodings, batch, max_fids = encode_padded_batch(doc_changes, mesh)
    arrays = shard_batch(batch, mesh)
    out = sharded_apply(arrays, max_fids, mesh)
    return encodings, out, len(doc_changes)


def reconcile_rows_sharded(doc_changes, mesh: Mesh, interpret: bool | None = None):
    """Mesh-sharded megakernel reconcile: the docs-minor row buffer's LANE
    axis (documents) is sharded over the mesh with `shard_map`, and each
    device runs `reconcile_rows_hash` on its own 128-aligned lane shard —
    the pod-scale shape of the streaming engine (no cross-shard
    communication: documents are independent; clock unions ride
    parallel/collective.py). Returns (hashes[n_docs] uint32, n_docs).

    The per-shard lane count is padded to a multiple of 128 * mesh size so
    every shard is a whole number of TPU lane tiles."""
    from ..engine.pack import pack_rows

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = mesh.devices.size
    # pad the docs axis so every shard is a whole 128-lane block
    _encs, batch, max_fids = encode_padded_batch(doc_changes, mesh,
                                                 multiple=128 * n)
    rows, dims, _d = pack_rows(batch, max_fids)
    fn = _sharded_rows_fn(mesh, dims, interpret)
    sharded = jax.device_put(rows, NamedSharding(mesh, P(None, DOCS_AXIS)))
    hashes = fn(sharded)
    return np.asarray(hashes)[:len(doc_changes)], len(doc_changes)


def reconcile_rows_sharded_bytes(doc_changes, mesh: Mesh,
                                 interpret: bool | None = None):
    """Mesh-sharded megakernel fed by the COMPACT BYTE WIRE: each dtype
    group of `pack.pack_rows_bytes` is reshaped to expose the document
    lane axis ([rows_dt, d_pad, itemsize] uint8), sharded on that axis,
    and widened to the int32 row buffer INSIDE each shard's program — so
    a pod ingests ~2.6x fewer wire bytes per chip than the wide path
    (reconcile_rows_sharded) with bit-identical hashes. No cross-shard
    communication, same as the wide variant. Returns
    (hashes[n_docs] uint32, n_docs)."""
    from ..engine.pack import pack_rows_compact

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = mesh.devices.size
    _encs, batch, max_fids = encode_padded_batch(doc_changes, mesh,
                                                 multiple=128 * n)
    (b8, b16, b32), meta, dims, _d = pack_rows_compact(batch, max_fids)
    # expose the document lane axis per dtype group: [rows_dt, d_pad, k]
    groups = tuple(
        np.ascontiguousarray(b).view(np.uint8).reshape(b.shape[0],
                                                       b.shape[1], k)
        if b.shape[0] else np.zeros((0, b.shape[1], k), np.uint8)
        for b, k in ((b8, 1), (b16, 2), (b32, 4)))
    fn = _sharded_bytes_fn(mesh, meta, dims, interpret)
    sh = NamedSharding(mesh, P(None, DOCS_AXIS, None))
    hashes = fn(*(jax.device_put(g, sh) for g in groups))
    return np.asarray(hashes)[:len(doc_changes)], len(doc_changes)


_SHARDED_ROWS_CACHE: dict = {}


def _sharded_bytes_fn(mesh: Mesh, meta: tuple, dims: tuple,
                      interpret: bool):
    # the Mesh itself is the cache key: its __eq__/__hash__ compare axis
    # names/shape and the actual Device objects, so a new Mesh over a
    # restarted backend can never alias a cached fn bound to dead devices
    # the way id(mesh) could
    key = ("bytes", mesh, meta, dims, interpret)
    fn = _SHARDED_ROWS_CACHE.get(key)
    if fn is not None:
        return fn
    import jax.numpy as jnp

    from ..engine.pack import apply_rows_hash_compact

    def body(g8, g16, g32):
        b8 = (jax.lax.bitcast_convert_type(g8[..., 0], jnp.int8)
              if g8.shape[0] else jnp.zeros((0, g8.shape[1]), jnp.int8))
        b16 = (jax.lax.bitcast_convert_type(g16, jnp.int16)
               if g16.shape[0] else jnp.zeros((0, g16.shape[1]), jnp.int16))
        b32 = (jax.lax.bitcast_convert_type(g32, jnp.int32)
               if g32.shape[0] else jnp.zeros((0, g32.shape[1]), jnp.int32))
        # one shared widen+hash implementation with the single-device
        # compact path (engine/pack.py) — no duplicated plumbing
        return apply_rows_hash_compact.__wrapped__(b8, b16, b32, meta,
                                                   dims, interpret)

    spec = P(None, DOCS_AXIS, None)
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=P(DOCS_AXIS), check_vma=False))
    _SHARDED_ROWS_CACHE[key] = fn
    return fn


def _sharded_rows_fn(mesh: Mesh, dims: tuple, interpret: bool):
    """Jitted shard_map'd megakernel, cached per (mesh, dims, interpret) so
    repeated reconciles do not retrace/recompile."""
    key = (mesh, dims, interpret)
    fn = _SHARDED_ROWS_CACHE.get(key)
    if fn is not None:
        return fn
    from functools import partial

    from ..engine.pallas_kernels import reconcile_rows_hash

    body = partial(reconcile_rows_hash.__wrapped__, dims=dims,
                   interpret=interpret)
    # vma check off: pallas_call's out_shape carries no varying-mesh-axes
    # annotation; the out_spec states the sharding explicitly
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(None, DOCS_AXIS),
                           out_specs=P(DOCS_AXIS), check_vma=False))
    _SHARDED_ROWS_CACHE[key] = fn
    return fn
