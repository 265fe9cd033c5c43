"""Roofline probe for the rows megakernel (VERDICT r3 #5, INTERNALS §4).

Measures device-resident bytes/s for `reconcile_rows_hash` (base blocked
kernel) and the XL doubly-blocked variant against the chip's HBM peak:
the kernel streams the whole docs-minor row buffer once per pass, so
row_bytes / device_s is the HBM-roofline proxy that separates kernel
headroom from link-bound ceiling (the quantity VERDICT r3 #5 asks for).

Timing uses one jit of P chained kernel calls (each pass's input depends on
the previous pass's hash, so XLA cannot CSE or reorder them) and ONE
readback, on the host's clock — an estimate until a profiler trace gives
the kernel's device time (ROADMAP S1).

Run on the TPU backend: `python -m automerge_tpu.perf roofline
[--docs N] [--passes P]` (or the repo-root `profile_roofline.py` shim).
Writes ROOFLINE.json at the repo root and prints one table row per probe.
Without a TPU it exits non-zero; a device kind missing from HBM_PEAK_GB_S
raises, it gets no default.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: peak HBM bandwidth in GB/s by `device_kind` (Google Cloud documentation,
#: "TPU v5e": 16 GB of HBM at 819 GB/s)
HBM_PEAK_GB_S = {"TPU v5 lite": 819, "TPU v5e": 819}


def hbm_peak_gb_s(device_kind: str) -> int:
    try:
        return HBM_PEAK_GB_S[device_kind]
    except KeyError:
        raise LookupError(
            f"no HBM peak on record for device kind {device_kind!r}; add it "
            "to automerge_tpu.perf.roofline.HBM_PEAK_GB_S with its source"
        ) from None

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _import_bench():
    """The workload generators live in the repo-root bench harness."""
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    import bench
    return bench


def _row_buffer(doc_changes):
    from automerge_tpu.engine.encode import encode_doc, stack_docs
    from automerge_tpu.engine.pack import pack_rows

    actors = sorted({c.actor for chs in doc_changes for c in chs})
    encs = [encode_doc(c, actors) for c in doc_changes]
    batch = stack_docs(encs)
    mf = batch.pop("max_fids")
    rows, dims, n = pack_rows(batch, mf)
    return rows, dims, n


def probe(name, doc_changes, force_xl, passes):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from automerge_tpu import metrics
    from automerge_tpu.engine.pack import rows_dims_eligible
    from automerge_tpu.engine.pallas_kernels import (_XL_BI,
                                                     reconcile_rows_hash)

    interpret = jax.default_backend() != "tpu"
    peak = None if interpret else hbm_peak_gb_s(jax.devices()[0].device_kind)
    rows, dims, n_docs = _row_buffer(doc_changes)
    I, A, LE = dims[0], dims[1], dims[2]
    if force_xl and I % _XL_BI:
        return {"probe": name, "skipped": f"I={I} not a multiple of "
                f"{_XL_BI} (XL block)"}
    if not force_xl and not rows_dims_eligible(I, A, LE):
        return {"probe": name, "skipped": f"dims I={I} A={A} LE={LE} "
                "exceed the base kernel's VMEM envelope"}

    # A fresh jit per probe is the point (each probe measures its own
    # compile+chain); the cache cannot help across distinct probe shapes.
    @partial(jax.jit, static_argnames=())  # graftlint: disable=jit-retrace
    def chained(r):
        acc = jnp.zeros((), jnp.uint32)
        for _ in range(passes):
            h = reconcile_rows_hash.__wrapped__(r, dims, interpret,
                                                force_xl=force_xl)
            acc = acc + h.sum()
            # serialize the passes: next input depends on this pass's hash
            r = r.at[0, 0].set(r[0, 0] + h[0].astype(jnp.int32))
        return acc

    kernel = f"roofline_chained_{'xl' if force_xl else 'base'}"
    r_dev = jnp.asarray(rows)
    # compile + first execution, through dispatch_jit so the probe's own
    # compile telemetry (cost/memory analysis) lands in the perf section
    np.asarray(metrics.dispatch_jit(kernel, chained, r_dev))
    t0 = time.perf_counter()
    np.asarray(chained(r_dev))          # timed: P passes, one readback
    total = time.perf_counter() - t0
    device_s = total / passes
    row_bytes = rows.shape[0] * rows.shape[1] * 4
    eff = row_bytes / device_s
    return {
        "probe": name,
        "kernel": "xl" if force_xl else "base",
        "docs": int(n_docs),
        "doc_lanes": int(rows.shape[1]),
        "dims": {"I": int(I), "A": int(A), "LE": int(LE)},
        "row_buffer_mb": round(row_bytes / 1e6, 2),
        "grid_steps": int(rows.shape[1] // 128),
        "vmem_block_mb": round(rows.shape[0] * 128 * 4 / 1e6, 2),
        "passes": passes,
        "device_s_per_pass": round(device_s, 6),
        "effective_GB_per_s": round(eff / 1e9, 3),
        "hbm_peak_GB_per_s": peak,
        "hbm_utilization_pct": (round(eff / (peak * 1e9) * 100, 2)
                                if peak else None),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(prog="automerge_tpu.perf roofline")
    ap.add_argument("--docs", type=int, default=10000)
    ap.add_argument("--xl-docs", type=int, default=2048)
    ap.add_argument("--passes", type=int, default=8)
    ap.add_argument("--interpret-smoke", action="store_true",
                    help="run tiny probes in pallas interpret mode on the "
                         "CPU backend — validates this module's plumbing "
                         "without a chip (timings are meaningless; nothing "
                         "is written)")
    args = ap.parse_args(argv)

    import jax
    if args.interpret_smoke:
        jax.config.update("jax_platforms", "cpu")   # before the backend
        backend = jax.default_backend()
        bench = _import_bench()
        bench._load_package()
        out = [probe("smoke-base", bench.gen_docset(64), False, 2),
               probe("smoke-trellis", bench.gen_trellis() * 8, False, 2)]
        print(json.dumps({"smoke": True, "backend": backend,
                          "probes": [{k: p[k] for k in p
                                      if k in ("probe", "skipped", "docs",
                                               "passes")}
                                     for p in out]}))
        skipped = [p["probe"] for p in out if "skipped" in p]
        if skipped:
            # a skipped probe validated nothing — fail loudly so the
            # smoke cannot green-light broken plumbing
            raise SystemExit(f"smoke probes skipped: {skipped}")
        return
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"backend is {backend}; the roofline probe needs "
                         "the TPU (pallas kernels + real HBM)")

    bench = _import_bench()
    bench._load_package()

    probes = []
    # base kernel at headline scale (config-5 shape)
    probes.append(probe(f"config5-{args.docs}docs",
                        bench.gen_docset(args.docs), False, args.passes))
    # wide-doc shape (config-2 trellis): base if it fits, XL forced on the
    # SAME batch for an apples-to-apples variant comparison
    trellis = bench.gen_trellis() * args.xl_docs
    probes.append(probe(f"trellis-{args.xl_docs}docs-base", trellis, False,
                        args.passes))
    probes.append(probe(f"trellis-{args.xl_docs}docs-xl", trellis, True,
                        args.passes))

    dev = jax.devices()[0]
    rec = {"backend": backend,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "probes": probes}
    with open(os.path.join(_ROOT, "ROOFLINE.json"), "w") as f:
        json.dump(rec, f, indent=1)
    for p in probes:
        if "skipped" in p:
            print(f"# {p['probe']}: SKIPPED ({p['skipped']})")
        else:
            print(f"# {p['probe']}: {p['kernel']} kernel, "
                  f"{p['row_buffer_mb']}MB rows, "
                  f"{p['device_s_per_pass']*1000:.2f}ms/pass, "
                  f"{p['effective_GB_per_s']} GB/s "
                  f"({p['hbm_utilization_pct']}% of HBM peak)")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
