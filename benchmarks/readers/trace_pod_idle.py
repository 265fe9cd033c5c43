"""Idle share of the cell's chips over the traced slice, the mean over all
of them where `trace_idle` reads the busiest one: 100 x (1 - the mean over
the cell's `chips` of the union of the intervals in which a device
operation ran / the slice). A chip whose plane holds no operation counts as
idle. Nothing where the trace holds no device plane."""


def read(args: dict, ctx: dict):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not tr["busy_by_chip"]:
        return None
    chips = ctx["chips"]
    busy = sorted(tr["busy_by_chip"].values(), reverse=True)[:chips]
    return 100.0 * (1.0 - sum(busy) / chips / tr["window_s"])
