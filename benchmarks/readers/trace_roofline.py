"""Roofline share of one kernel over the traced slice: the least time the
chip could take for the bytes of its calls over the device time the trace
gives them, in percent. Everything that names the kernel is in the
metric's own file: `event`, a pattern that finds the kernel's operations
by their full names on the device's operations line; `shape`, a pattern
whose groups are the numbers of the call's shape, read from that name;
`bytes`, the function of `peaks.py` that gives the least bytes for them.
The byte bound is the roofline for a kernel with no matrix product.
Nothing where the slice holds no call of the kernel; a call whose name
does not give its shape is an error, since the share would leave out its
bytes and not its time."""

import re

import peaks


def read(args: dict, ctx: dict):
    tr = ctx["trace"]
    if not tr:
        return None
    event, shape = re.compile(args["event"]), re.compile(args["shape"])
    least_bytes = getattr(peaks, args["bytes"])
    total_bytes, device_s = 0, 0.0
    for name, row in tr["events"].items():
        if not event.search(name):
            continue
        m = shape.search(name)
        if not m:
            raise ValueError(f"no shape {args['shape']!r} in {name[:200]!r}")
        total_bytes += row["calls"] * least_bytes(*map(int, m.groups()))
        device_s += row["device_s"]
    if not device_s:
        return None
    least_s = total_bytes / peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
