"""Idle share of the busiest chip over the traced slice: 100 x (1 - union
of the intervals in which a device operation ran / the slice). Nothing
where the trace holds no device operation."""


def read(args: dict, ctx: dict):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not tr["busy_by_chip"]:
        return None
    return 100.0 * (1.0 - max(tr["busy_by_chip"].values()) / tr["window_s"])
