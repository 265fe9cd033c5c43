"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is `workloads/<cell>.json`: a configuration (`configs/`), a traffic
mix (`traffic/`, issued by its driver under `drivers/`), and the chips it
needs. A configuration may name its fleet kind (`fleets/`) and its check
(`checks/`), a mix its schedule (`schedules/`); one that names none gets
`fleet.py`, `check.py` with `reference.py`, `traffic.py`. The run touches
JAX first and ends with exit code 2, printing no result, unless JAX reports
a TPU with the cell's chips: there is no CPU path here (the tests call
`run_cell` with devices passed in). It then builds the fleet from the seed,
loads it, warms up through the same driver, measures for `--seconds`,
decides `correct` (the check) and prints one JSON line a stage and, last,
the result line.

With `--trace 0` the result's metrics are the end-to-end ones, taken here
from the host's clock; with `--trace 1` a slice of the window is traced by
the JAX profiler and the metrics are the per-layer ones, each by the reader
its `metrics/<name>.json` names (`readers/`); a metric that lists the cell
and reads nothing there ends the run with exit code 1 and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import fleet as fleetlib  # noqa: E402
import tracefile  # noqa: E402
import traffic  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_START_SHARE = 0.4   # the traced slice begins this far into the window
TRACE_SLICE_S = 3.0
STATE_SAMPLE = 64


class RunFailed(RuntimeError):
    """The run cannot give a result: it ends with exit code 1 and no
    result line."""


def emit(rec: dict) -> None:
    print(json.dumps(rec, sort_keys=True, default=str), flush=True)


def load_by_path(kind: str, name: str, root: str = HERE):
    """`<root>/<kind>/<name>.py` as a module: drivers, readers, fleet
    kinds, schedules and checks are files found by name."""
    path = os.path.join(root, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def claim_devices(chips: int) -> list:
    """First touch of JAX: a TPU with the cell's chips, or no run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunFailed(f"the benchmark needs a TPU; JAX reports "
                        f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise RunFailed(f"the cell needs {chips} chips; JAX reports "
                        f"{len(devs)}")
    return devs


def seam(data: dict, key: str, kind: str, default, root: str = HERE):
    """The module a data file names under `key` (`<kind>/<name>.py`), or
    `default`, today's code, where it names none."""
    return load_by_path(kind, data[key], root) if key in data else default


def metric_holds(m: dict, cell_name: str, mixes: set, service: str) -> bool:
    """Whether a metric's file holds for a cell. The file says so by what
    the cell is: `mixes`, the traffic mixes under which the metric finds
    something to read, and `services`, the service kinds; either may be
    left out and then does not narrow. A file may list cells by name
    instead (`workloads`), and one that gives none of the three holds for
    every cell."""
    if "mixes" not in m and "services" not in m:
        return "workloads" not in m or cell_name in m["workloads"]
    return bool(mixes & set(m.get("mixes", mixes))) \
        and service in m.get("services", (service,))


def cell_metrics(cell_name: str, root: str = HERE) -> list:
    """The per-layer metrics this cell reports: every `metrics/*.json`
    that holds for it (`metric_holds`). The cell's mix counts under its
    own name and under every name in its file's `reports_as`: a mix built
    from accepted ones takes their metrics as data."""
    cell = fleetlib.load_json("workloads", cell_name, root)
    mix = fleetlib.load_json("traffic", cell["traffic"], root)
    mixes = {cell["traffic"], *mix.get("reports_as", ())}
    service = fleetlib.load_json("configs", cell["config"], root)["service"]
    out = []
    for fn in sorted(os.listdir(os.path.join(root, "metrics"))):
        if not fn.endswith(".json"):
            continue
        m = fleetlib.load_json("metrics", fn[:-5], root)
        if metric_holds(m, cell_name, mixes, service):
            out.append(m)
    return out


def percentile(values: list, q: float) -> float:
    """The q-th percentile, nearest rank."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(-(-q * len(s) // 1)) - 1))]


def end_to_end(window: dict, setup_s: float) -> dict:
    reqs = [q for q in window["requests"] if q.error is None]
    if not reqs:
        raise RunFailed("no request of the window returned")
    used = window["requests"][-1].returned - window["begin"]
    acks = [(q.returned - q.submitted) * 1e3 for q in reqs]
    return {
        "ops_per_s": {"value": sum(q.ops for q in reqs) / used,
                      "unit": "ops/s"},
        "ack_p50_ms": {"value": statistics.median(acks), "unit": "ms"},
        "ack_p95_ms": {"value": percentile(acks, 0.95), "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }


class Tracer:
    """Starts the JAX profiler once the window is `begin_after_s` old and
    stops it `slice_s` later, between requests. The Python tracer is off (it
    slows the host by half and writes millions of events); the program's own
    `TraceAnnotation` spans and the runtime's stay. From one `between` call
    to the next the harness holds a span of its own, `bench_loop`: one turn
    of the driver's loop, a request with the building of it. The reducer
    takes the traced window from the first of them to the last."""

    def __init__(self, begin_after_s: float, slice_s: float):
        self.begin_after_s, self.slice_s = begin_after_s, slice_s
        self.t_window = None
        self.started = self.stopped = None
        self._span = None

    def _close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def between(self, now: float) -> None:
        import jax
        if self.t_window is None:
            self.t_window = now
        self._close_span()
        if self.started is None and now - self.t_window >= self.begin_after_s:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.started = time.perf_counter()
        elif self.started is not None and self.stopped is None \
                and now - self.started >= self.slice_s:
            self.stop()
        if self.started is not None and self.stopped is None:
            self._span = jax.profiler.TraceAnnotation(tracefile.LOOP_SPAN)
            self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._close_span()
        if self.started is not None and self.stopped is None:
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()


def device_record(devices, used: int) -> dict:
    peak = 0
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell_name: str, seed: int, seconds: float, trace: int, devices,
             root: str = HERE, steer=None, t_start: float | None = None,
             max_requests: int = 100_000, may_miss=()) -> dict:
    """Set-up, window and checks of one run on `devices`; returns the
    result line as a dict. `steer(svc)` lets a test put the service on the
    road the chip takes, or return another to stand in its place; `root` is
    where the data files are found. A per-layer metric that lists the cell
    and reads nothing ends the run, unless a test on the CPU names it in
    `may_miss` (a CPU trace has no device plane)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = fleetlib.load_json("workloads", cell_name, root)
    config = fleetlib.load_json("configs", cell["config"], root)
    mix = fleetlib.load_json("traffic", cell["traffic"], root)
    driver = load_by_path("drivers", mix["driver"], root)
    fleet_kind = seam(config, "fleet_kind", "fleets", fleetlib, root)
    scheduler = seam(mix, "schedule", "schedules", traffic, root)
    checker = seam(config, "check", "checks", check, root)
    metric_files = cell_metrics(cell_name, root) if trace else []
    readers = {m["reader"]: load_by_path("readers", m["reader"], root)
               for m in metric_files}

    from automerge_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    import jax
    # every executable is kept, also where JAX_COMPILATION_CACHE_DIR is
    # set and the program therefore sets nothing: the small programs of a
    # run add up, and a run after the first has to find each of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    counters_at_start = fleetlib.counters()
    t = time.perf_counter()
    fleet = fleet_kind.make(config, seed)
    schedule = scheduler.make(mix, fleet, seed, root)
    emit({"stage": "fleet", "seconds": round(time.perf_counter() - t, 2),
          "docs": len(fleet.doc_ids), "cache_dir": cache_dir,
          "cache_entries": compile_cache.entries(cache_dir)})

    svc = fleetlib.new_service(config, devices)
    if steer is not None:
        svc = steer(svc) or svc
    try:
        t = time.perf_counter()
        n_rounds = 0
        for round_ in fleet.load_rounds():
            fleetlib.apply_round(svc, round_)
            n_rounds += 1
        del round_
        untouched_before = checker.read_untouched(svc, fleet)
        emit({"stage": "load", "seconds": round(time.perf_counter() - t, 2),
              "load_rounds": n_rounds, **fleet.load_line(),
              "dims": fleetlib.resident_dims(svc),
              "resident_bytes": sum(e.resident_bytes()
                                    for e in fleetlib.engines(svc))})

        t = time.perf_counter()
        n_warm = mix["warmup_requests"]
        warm = driver.run(svc, fleet, schedule, first=0, max_requests=n_warm)
        svc.hashes()
        before_probe = fleetlib.counters()
        probe = driver.run(svc, fleet, schedule, first=n_warm,
                           max_requests=1)
        left = {k: v for k, v in fleetlib.counter_delta(
            before_probe, fleetlib.counters()).items()
            if k.startswith("compiles.") and v}
        emit({"stage": "warmup", "seconds": round(time.perf_counter() - t, 2),
              "requests": len(warm["requests"]) + len(probe["requests"]),
              "compiled_after_warmup": left})
        for w in (warm, probe):
            if w["stopped"] != "max_requests":
                raise RunFailed(f"the warm-up stopped on {w['stopped']}: "
                                f"{w['requests'][-1:]}")
        if left:
            raise RunFailed(f"the warm-up left programs uncompiled that the "
                            f"next request compiled: {left}")

        first = n_warm + 1
        dims_before = fleetlib.resident_dims(svc)
        tracer = Tracer(TRACE_START_SHARE * seconds,
                        min(TRACE_SLICE_S, 0.5 * seconds)) if trace else None
        counters_before = fleetlib.counters()
        setup_s = time.perf_counter() - t_start
        window = driver.run(svc, fleet, schedule, first=first,
                            max_requests=max_requests, seconds=seconds,
                            between=tracer.between if tracer else None)
        if tracer:
            tracer.stop()
            if tracer.started is None:
                raise RunFailed("the window ended before the traced slice "
                                "began")
        counters_after = fleetlib.counters()
        dims_after = fleetlib.resident_dims(svc)
        device = device_record(devices, cell["chips"])
        delta = fleetlib.counter_delta(counters_before, counters_after)
        reqs = window["requests"]
        used_s = (reqs[-1].returned if reqs else window["end"]) \
            - window["begin"]
        emit({"stage": "window", "seconds": round(used_s, 3),
              "requests": len(reqs), "stopped": window["stopped"],
              "ops": sum(q.ops for q in reqs),
              "building_s": round(window["building_s"], 3),
              "dims_before": dims_before, "dims_after": dims_after,
              "compiles_in_window": {k: v for k, v in delta.items()
                                     if k.startswith("compiles.") and v},
              "phases_s": {k[6:]: round(v, 3) for k, v in delta.items()
                           if k.startswith("phase.") and v},
              "rounds_flushed": delta.get("sync_rounds_flushed", 0),
              "ops_ingested": delta.get("sync_ops_ingested", 0),
              "megabatch_rounds": delta.get("engine_megabatch_rounds", 0)})
        if dims_after != dims_before and fleet.dims_fixed:
            raise RunFailed(f"the resident dims changed inside the window: "
                            f"{dims_before} -> {dims_after}")
        if not reqs:
            raise RunFailed("the window issued no request")

        t = time.perf_counter()
        read = checker.read_program(svc, fleet, seed, STATE_SAMPLE)
        fallbacks = sum(counters_after.get(k, 0) - counters_at_start.get(k, 0)
                        for k in checker.FALLBACK_COUNTERS)
    finally:
        svc.close()
    del svc
    acked = list(range(first)) + [q.number for q in reqs if q.error is None]
    sent, origin = fleet.replay(schedule, acked)
    verdict = checker.decide(read, fleet, sent, origin, untouched_before,
                             reqs, fallbacks)
    del sent, origin
    emit({"stage": "check", "seconds": round(time.perf_counter() - t, 2),
          "compared": verdict["sizes"]})

    if trace:
        t = time.perf_counter()
        reduced = tracefile.reduce_dir(TRACE_DIR, cell["chips"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"window_s": used_s, "delta": delta, "trace": reduced,
               "device_kind": device["kind"], "chips": cell["chips"]}
        metrics, missing = {}, []
        for m in metric_files:
            value = readers[m["reader"]].read(m.get("args", {}), ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif m["name"] not in may_miss:
                missing.append(m["name"])
        if missing:
            raise RunFailed(
                f"per-layer metrics that list {cell_name} read nothing in "
                f"its traced run: {missing}; device operations seen: "
                f"{sorted(reduced['ops_s'])[:12]}")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        emit({"stage": "trace", "seconds": round(time.perf_counter() - t, 2),
              "planes": reduced["planes"], "events": reduced["n_events"]})
    else:
        metrics = end_to_end(window, setup_s)
        emit({"stage": "samples", "ack_samples": len(reqs),
              "ops": sum(q.ops for q in reqs)})

    result = {"correct": verdict["correct"], "attempted": len(reqs),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = tracefile.breakdown(reduced)
    result["compared"] = verdict["compared"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = fleetlib.load_json("workloads", args.workload)
        devices = claim_devices(cell["chips"])
    except (RunFailed, OSError) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                          devices, t_start=T_START)
    except RunFailed as e:
        emit({"stage": "failed", "error": str(e)})
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
