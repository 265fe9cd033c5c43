"""Many seeds of one cell in one process: the proof runs of `correct`.

    python3 benchmarks/prove.py --workload <cell> --seeds 1,2,3 --seconds 5
    python3 benchmarks/prove.py --workload <cell> --seeds 1,2,3 --seconds 5 --control 1

Without `--control` each seed is a whole run of the program (`run_cell`, as
`run.py` makes it) and has to come out correct. With it, each seed is run
once for every control the configuration's check declares (`CONTROLS`; in
`check.py` every guarantee `reference.RefService` can break), the reference
in the program's place at the cell's own size and load, and each has to
come out not correct. One line a run: the seed, `correct` and the numbers
compared. Exit code 0 only if every run came out as it has to. The
benchmark's own runs never call this; it is how the limits of PERF.md
section 2 were read, and how a later PR reads them again.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

import run


def main(argv=None, *, root: str = run.HERE, devices=None, steer=None,
         max_requests: int = 100_000) -> int:
    """`root`, `devices`, `steer` and `max_requests` are for the tests:
    the data files of a copy, the CPU's devices, and what steers a sound
    run's service onto the road the chip takes."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = run.fleetlib.load_json("workloads", args.workload, root)
    if devices is None:
        try:
            devices = run.claim_devices(cell["chips"])
        except run.RunFailed as e:
            print(f"benchmarks/prove.py: {e}", file=sys.stderr)
            return 2
    config = run.fleetlib.load_json("configs", cell["config"], root)
    controls = run.seam(config, "check", "checks", run.check, root).CONTROLS
    kinds = list(controls) if args.control else [None]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in kinds:
            def stand_in(svc, kind=kind):
                if kind is None:
                    return steer(svc) if steer else None
                svc.close()
                return controls[kind]()
            stages = io.StringIO()
            with contextlib.redirect_stdout(stages):
                try:
                    res = run.run_cell(args.workload, seed, args.seconds, 0,
                                       devices, root=root, steer=stand_in,
                                       max_requests=max_requests)
                except run.RunFailed as e:
                    res = {"correct": None, "error": str(e), "compared": {},
                           "attempted": 0, "failed": 0, "metrics": {}}
            want = kind is None
            ok = ok and res["correct"] is want
            print(json.dumps({
                "seed": seed, "control": kind, "correct": res["correct"],
                "as_it_has_to": res["correct"] is want,
                "attempted": res["attempted"], "failed": res["failed"],
                "compared": {k: v["value"]
                             for k, v in res["compared"].items()},
                "error": res.get("error"),
                "metrics": {k: round(v["value"], 3)
                            for k, v in res["metrics"].items()}}),
                flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
