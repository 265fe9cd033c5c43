"""How evenly the cell's chips worked over the traced slice: the busy time
of the least busy of its `chips` chips over that of the busiest, in percent
(100 = every chip worked as long). 0 where fewer than `chips` device planes
hold an operation: a chip that never worked. Nothing where the trace holds
no device plane."""


def read(args: dict, ctx: dict):
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not tr["busy_by_chip"]:
        return None
    chips = ctx["chips"]
    busy = sorted(tr["busy_by_chip"].values(), reverse=True)[:chips]
    if len(busy) < chips or not busy[0]:
        return 0.0
    return 100.0 * busy[-1] / busy[0]
