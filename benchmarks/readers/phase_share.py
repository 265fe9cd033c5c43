"""Seconds of the program's perfscope phase `phase` inside the window, as a
percentage of the window. Host-clock wall time of a host phase; never a
device time. Nothing where the phase was never entered in the window."""


def read(args: dict, ctx: dict):
    s = ctx["delta"].get("phase." + args["phase"], 0.0)
    if s <= 0 or not ctx["window_s"]:
        return None
    return 100.0 * s / ctx["window_s"]
