"""From a JAX profiler trace to the numbers the per-layer readers take:
device busy time for each chip, the traced window, calls and device time
of each device operation by its full name, the device operations that took
most time and the longest idle gaps by what the host was doing. It knows
no kernel: which operations are a kernel's, and the bytes of a call, are
in the metric's own file (`readers/trace_roofline.py`).

The reduction works on a plain structure, so that it can be checked on a
small recorded slice kept as JSON (tests/fixtures):

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns, {stat: value}], ...]}]}]}

`from_xplane` makes that structure from the profiler's `.xplane.pb` with
nothing but JAX (`jax.profiler.ProfileData`).

    python3 benchmarks/tracefile.py <trace dir> [slice.json [seconds]]

prints what a trace holds (planes, lines, the commonest names, the stats'
keys) and what `reduce` makes of it, and can cut a slice of its first
`seconds` for a fixture.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
KEPT_STATS = ("hlo_op", "hlo_module", "program_id", "run_id",
              "shape_with_layout", "bytes_accessed", "flops", "tf_op",
              "long_name", "hlo_category", "model_flops")


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_xplane(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(pl.name))
        if not is_dev and not pl.name.startswith("/host:"):
            continue
        lines = []
        for ln in pl.lines:
            events = []
            for e in ln.events:
                stats = {}
                if is_dev:
                    for k, v in e.stats:
                        if k in KEPT_STATS:
                            stats[k] = v
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def union_s(intervals: list) -> tuple:
    """Seconds covered by the union of [start_ns, end_ns] intervals, and
    the merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) * 1e-9, merged


LOOP_SPAN = "bench_loop"
_LHS = re.compile(r"^%?([A-Za-z_][\w.-]*?)(?:\.\d+)? = ")
_OPERAND = re.compile(r"\((\w+\[[\d,]*\])")


def op_kernel(op_name: str) -> str | None:
    """`%reconcile_rows_hash.1 = s32[1,768]... custom-call(...)` ->
    `reconcile_rows_hash`: the kernel or HLO instruction an event of the
    device's operations line ran, without its instance number."""
    m = _LHS.match(op_name)
    return m.group(1) if m else None


def short_name(op_name: str) -> str:
    """The instruction's name with its first operand's shape, for the
    breakdown: `reconcile_rows_hash s32[3076,768]`."""
    k = op_kernel(op_name) or op_name[:60]
    m = _OPERAND.search(op_name)
    return f"{k} {m.group(1)}" if m else k


def reduce(data: dict, chips: int) -> dict:
    """The trace's numbers over the traced window: from the start of the
    harness's first `bench_loop` span to the end of its last (the whole
    trace where there is none). `busy_s` is the mean over the `chips`
    busiest device planes of the union of their operations' intervals.
    `events` holds, for each full name on a device's operations line, its
    calls and device seconds inside the window."""
    host_events = []
    device_lines = {}
    n_events = 0
    for pl in data["planes"]:
        m = DEVICE_PLANE.match(pl["name"])
        for ln in pl["lines"]:
            n_events += len(ln["events"])
            if m and ln["name"] == OPS_LINE:
                device_lines[m.group(1)] = ln["events"]
            elif not m:
                host_events.extend(ev for ev in ln["events"] if ev[2] > 0)
    loops = [ev for ev in host_events if ev[0] == LOOP_SPAN]
    spans = loops or host_events + [
        ev for evs in device_lines.values() for ev in evs]
    if not spans:
        return {"planes": [pl["name"] for pl in data["planes"]],
                "n_events": n_events, "window_s": 0.0, "busy_by_chip": {},
                "busy_s": 0.0, "events": {}, "ops_s": {}, "gaps": []}
    t0 = min(ev[1] for ev in spans)
    t1 = max(ev[1] + ev[2] for ev in spans)
    busy_by_chip, merged_by_chip = {}, {}
    ops_s: dict = {}
    by_name: dict = {}
    for chip, events in device_lines.items():
        iv = []
        for name, start, dur, _st in events:
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s:
                continue
            iv.append((s, e))
            sec = (e - s) * 1e-9
            short = short_name(name)
            ops_s[short] = ops_s.get(short, 0.0) + sec
            k = by_name.setdefault(name, {"calls": 0, "device_s": 0.0})
            k["calls"] += 1
            k["device_s"] += sec
        busy_by_chip[chip], merged_by_chip[chip] = union_s(iv)
    busiest = sorted(busy_by_chip.values(), reverse=True)[:chips]
    gaps = []
    if busy_by_chip:
        chip = max(busy_by_chip, key=busy_by_chip.get)
        edges = [t0] + [x for iv in merged_by_chip[chip] for x in iv] + [t1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = idle_by_span(idle, _harness_thread(data))
    return {
        "planes": [pl["name"] for pl in data["planes"]],
        "n_events": n_events, "window_s": (t1 - t0) * 1e-9,
        "busy_by_chip": busy_by_chip,
        "busy_s": sum(busiest) / len(busiest) if busiest else 0.0,
        "events": by_name, "ops_s": ops_s, "gaps": gaps,
    }


def _harness_thread(data: dict) -> list:
    """The events of the host thread that holds the harness's loop spans
    (the thread that calls the service); every host event where no line
    holds one."""
    every = []
    for pl in data["planes"]:
        if DEVICE_PLANE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            events = [ev for ev in ln["events"] if ev[2] > 0]
            if any(ev[0] == LOOP_SPAN for ev in events):
                return events
            every.extend(events)
    return every


def idle_by_span(idle: list, events: list) -> list:
    """The device's idle intervals, shared out over what the host thread
    was doing: each instant goes to the innermost span open at it. Returns
    [[span name, idle seconds]], most first."""
    marks = []
    for i, (_n, s, d, _st) in enumerate(events):
        marks.append((s, 1, -d, i))
        marks.append((s + d, 0, d, i))
    marks.sort()
    starts = [iv[0] for iv in idle]
    out: dict = {}

    def credit(name, a, b):
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while j < len(idle) and idle[j][0] < b:
            ov = min(b, idle[j][1]) - max(a, idle[j][0])
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov * 1e-9
            j += 1

    stack: list = []
    prev = idle[0][0] if idle else None
    for t, opens, _d, i in marks:
        if prev is not None and t > prev:
            credit(events[stack[-1]][0] if stack else "no host span",
                   prev, t)
            prev = t
        if opens:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    if idle and prev < idle[-1][1]:
        credit("no host span", prev, idle[-1][1])
    return [[n, sec] for n, sec in sorted(out.items(),
                                          key=lambda kv: -kv[1])]


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The device operations with most time, and the idle time of the
    busiest chip by the innermost span the calling host thread was in."""
    ops = sorted(reduced["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n[:120], s] for n, s in reduced["gaps"][:top]]}


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce(from_xplane(newest_xplane(trace_dir)), chips)


def cut(data: dict, seconds: float) -> dict:
    """The events that begin in the first `seconds` of the trace."""
    starts = [ev[1] for pl in data["planes"] for ln in pl["lines"]
              for ev in ln["events"]]
    if not starts:
        return data
    end = min(starts) + seconds * 1e9
    return {"planes": [{"name": pl["name"], "lines": [
        {"name": ln["name"],
         "events": [ev for ev in ln["events"] if ev[1] < end]}
        for ln in pl["lines"]]} for pl in data["planes"]]}


def describe(data: dict) -> None:
    for pl in data["planes"]:
        print("PLANE", pl["name"])
        for ln in pl["lines"]:
            names: dict = {}
            keys: set = set()
            for name, _s, d, st in ln["events"]:
                n = names.setdefault(name, [0, 0.0])
                n[0] += 1
                n[1] += d
                keys.update(st)
            print("  LINE", ln["name"], len(ln["events"]), sorted(keys))
            for name, (n, d) in sorted(names.items(),
                                       key=lambda kv: -kv[1][1])[:12]:
                print(f"     {n:6d} {d * 1e-6:12.3f} ms  {name[:100]}")
            for ev in ln["events"][:2]:
                print("     e.g.", json.dumps(ev)[:600])


if __name__ == "__main__":
    d = from_xplane(newest_xplane(sys.argv[1]))
    describe(d)
    print(json.dumps(reduce(d, 1), indent=1)[:4000])
    if len(sys.argv) > 2:
        sl = cut(d, float(sys.argv[3]) if len(sys.argv) > 3 else 0.5)
        with open(sys.argv[2], "w", encoding="utf-8") as f:
            json.dump(sl, f)
