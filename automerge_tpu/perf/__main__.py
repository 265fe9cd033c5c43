"""CLI for the performance plane: `python -m automerge_tpu.perf
{report,check,contention,doctor,explain,top,dispatch,tenant,trace,
remediate}` (docs/OBSERVABILITY.md "Performance
plane" / "Contention & convergence lag" / "Fleet health" / "Per-doc
ledger & perf explain" / "Remediation plane" / "Dispatch-efficiency
ledger" / "Tenant attribution plane" / "Trace plane").

- `doctor`  — ranked root-cause report: live against a fleet
  (--connect), or post-mortem against a BENCH_DETAIL.json / flight-
  recorder dump (--post-mortem; default: the repo BENCH_DETAIL.json).
- `explain` — per-DOC causal convergence debugger over the docledger
  sections: `perf explain <doc>` names the blocking cause (frame loss
  at the sender, epoch-buffered, causal queue, stalled connection);
  without a doc it lists the worst-lagging docs. Same three modes as
  the doctor (local capture, --connect, --post-mortem).
- `top`     — live terminal dashboard (fleet table, SLO verdict strip,
  sparklines, per-doc hot list) driven by the fleet collector
  (perf/fleet.py).
- `dispatch` — dispatch-efficiency report over the kernel-routing
  ledger (engine/dispatchledger.py): amplification, padding waste,
  per-kernel attribution, and the megabatch-opportunity projection.
  Same three modes as the doctor, plus `--smoke` (verify.sh stage 2).
- `tenant`  — per-tenant cost/latency/isolation report over the tenant
  attribution plane (sync/tenantledger.py): ingress/dispatch/wire
  shares, governor shed splits, converge-lag rings, and the
  attribution-sum check. Same modes as `dispatch`, plus `--smoke`.
- `trace`   — stage-latency report over the trace plane
  (utils/tracer.py): per-stage p50/p99, the end-to-end critical-path
  distribution, and waterfall renderings of the slowest stitched
  exemplars. Same modes as `dispatch`, plus `--smoke` (a real
  two-service TCP fleet with one stitched trace asserted).
- `remediate` — the chaos-recovery smoke (verify.sh stage 2): injects
  one conn_kill into a supervised TCP link and asserts the fleet
  self-heals (perf/remediate.py).
- `megabatch` — the fused multi-doc round smoke (verify.sh stage 2):
  a mixed-shape fleet storm through the megabatch path, byte-equal
  against the disabled path (perf/megabatchplane.py).

Exit codes: 0 = ok (including a gracefully skipped check), 1 = the
regression gate tripped, 2 = usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import history


def _cmd_check(argv) -> int:
    ap = argparse.ArgumentParser(prog="automerge_tpu.perf check")
    ap.add_argument("--history", default=None,
                    help="path to bench_history.jsonl "
                         "(default: repo root)")
    ap.add_argument("--record", default=None,
                    help="judge this JSON record file instead of the last "
                         "history entry (it is compared against the whole "
                         "file)")
    ap.add_argument("--window", type=int, default=history.DEFAULT_WINDOW)
    ap.add_argument("--threshold-pct", type=float,
                    default=history.DEFAULT_THRESHOLD_PCT,
                    help="fail when throughput drops below "
                         "(1 - pct/100) x rolling median")
    ap.add_argument("--compile-growth-pct", type=float,
                    default=history.DEFAULT_COMPILE_GROWTH_PCT,
                    help="fail when total compiles exceed the rolling "
                         "median by more than pct (+2 absolute slack)")
    ap.add_argument("--hash-growth-pct", type=float,
                    default=history.DEFAULT_HASH_GROWTH_PCT,
                    help="fail when the clean-fleet convergence read "
                         "(fleet_hashes_s) exceeds the rolling median by "
                         "more than pct (+0.25s absolute slack)")
    ap.add_argument("--no-backfill", action="store_true",
                    help="do not create the history file from the "
                         "committed BENCH_r0*.json captures when missing")
    args = ap.parse_args(argv)

    path = args.history or history.history_path()
    if not args.no_backfill and not os.path.exists(path):
        n = history.ensure_backfilled(path=path)
        if n:
            print(f"perf check: backfilled {n} records from committed "
                  f"BENCH_r0*.json captures -> {path}")
    record = None
    if args.record:
        try:
            with open(args.record) as f:
                record = json.load(f)
        except (OSError, ValueError) as e:
            print(f"perf check: cannot read --record {args.record}: {e}",
                  file=sys.stderr)
            return 2
        if "schema" not in record:   # a raw bench final/compact record
            # stamp_host=False: the capture's provenance is whatever the
            # record itself says (bench stamps `host` at run time) — the
            # CHECKING machine's identity must not be invented onto a
            # record produced elsewhere
            record = history.record_from_bench(record, source=args.record,
                                               stamp_host=False)
    rc, lines = history.check(
        path=path, record=record, window=args.window,
        threshold_pct=args.threshold_pct,
        compile_growth_pct=args.compile_growth_pct,
        hash_growth_pct=args.hash_growth_pct)
    print("\n".join(lines))
    print("PERFCHECK", "FAIL" if rc else "OK")
    return rc


def _cmd_report(argv) -> int:
    ap = argparse.ArgumentParser(prog="automerge_tpu.perf report")
    ap.add_argument("--history", default=None)
    ap.add_argument("--no-backfill", action="store_true")
    args = ap.parse_args(argv)
    path = args.history or history.history_path()
    if not args.no_backfill and not os.path.exists(path):
        history.ensure_backfilled(path=path)
    records = history.load(path)
    if not records:
        print("perf report: no history "
              f"({path} is missing or empty; run bench.py)")
        return 0
    print(f"# bench history — {len(records)} records ({path})")
    print(f"{'#':>3} {'source':<28} {'backend':<8} "
          f"{'ops/sec':>12} {'vs_base':>8}  configs(speedup)")
    for i, r in enumerate(records):
        cfgs = r.get("configs") or {}
        cfg_s = " ".join(
            f"{c}:{(cfgs[c] or {}).get('speedup')}"
            for c in sorted(cfgs, key=lambda c: (len(c), c))
            if (cfgs[c] or {}).get("speedup") is not None)
        value = r.get("value")
        print(f"{i:>3} {str(r.get('source', '?'))[:28]:<28} "
              f"{str(r.get('backend', '?')):<8} "
              f"{value if value is not None else '-':>12} "
              f"{str(r.get('vs_baseline', '-')):>8}  {cfg_s}")
    last = records[-1]
    perf = last.get("perf")
    if perf:
        print(f"# latest perf: {perf.get('compiles_total')} compiles "
              f"across {len(perf.get('kernels') or {})} kernels: "
              + ", ".join(f"{k}={v}"
                          for k, v in sorted(
                              (perf.get("kernels") or {}).items())))
    # the in-repo detail sidecar, when the last bench run left one
    detail = os.path.join(os.path.dirname(path), "BENCH_DETAIL.json")
    if os.path.exists(detail):
        print(f"# full per-config breakdown: {detail}")
        # the contention & convergence-lag section (informational; the
        # quantified baseline ROADMAP #1's ingestion refactor lands
        # against — docs/OBSERVABILITY.md "Contention & convergence lag")
        from . import contention
        for line in contention.report_lines(detail_path=detail):
            print(line)
    return 0


def _cmd_contention(argv) -> int:
    ap = argparse.ArgumentParser(prog="automerge_tpu.perf contention")
    ap.add_argument("--detail", default=None,
                    help="BENCH_DETAIL.json to read per-config snapshots "
                         "from (default: repo root)")
    ap.add_argument("--snapshot", default=None,
                    help="render a raw metrics.snapshot() JSON file "
                         "instead of the bench detail")
    ap.add_argument("--config", default=None,
                    help="restrict the detail report to one bench config")
    args = ap.parse_args(argv)
    from . import contention
    print("\n".join(contention.report_lines(
        detail_path=args.detail, snapshot_path=args.snapshot,
        config=args.config)))
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    commands = {
        "check": _cmd_check,
        "report": _cmd_report,
        "contention": _cmd_contention,
    }
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd in commands:
        return commands[cmd](rest)
    if cmd == "doctor":
        from . import doctor
        return doctor.main(rest)
    if cmd == "explain":
        from . import explain
        return explain.main(rest)
    if cmd == "top":
        from . import top
        return top.main(rest)
    if cmd == "dispatch":
        from . import dispatchplane
        return dispatchplane.main(rest)
    if cmd == "tenant":
        from . import tenantplane
        return tenantplane.main(rest)
    if cmd == "trace":
        from . import traceplane
        return traceplane.main(rest)
    if cmd == "remediate":
        # the chaos-recovery smoke (verify.sh stage 2): one injected
        # fault, assert the supervised link self-heals
        from . import remediate
        return remediate.smoke_main(rest)
    if cmd == "move":
        # the move-plane smoke (verify.sh stage 2): concurrent cycle
        # storm on two services, convergence + kernel parity asserted
        from . import moveplane
        return moveplane.smoke_main(rest)
    if cmd == "bootstrap":
        # the replica-bootstrap smoke (verify.sh stage 2): deep-history
        # doc -> snapshot -> cold-boot a fresh replica, byte-equal hashes
        from . import bootstrap
        return bootstrap.smoke_main(rest)
    if cmd == "race":
        # the race-plane smoke (verify.sh stage 2): a threaded sync
        # storm under AMTPU_LOCKSAN=1 — zero sanitizer violations,
        # sanitizer overhead < 5%
        from . import raceplane
        return raceplane.smoke_main(rest)
    if cmd == "megabatch":
        # the megabatch-plane smoke (verify.sh stage 2): a mixed-shape
        # fleet storm through the fused multi-doc round, byte-equal
        # against the AMTPU_MEGABATCH=0 path, occupancy asserted
        from . import megabatchplane
        return megabatchplane.smoke_main(rest)
    print(f"unknown command {cmd!r}; expected one of "
          "report, check, contention, doctor, explain, top, dispatch, "
          "tenant, trace, remediate, move, bootstrap, race, megabatch",
          file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
