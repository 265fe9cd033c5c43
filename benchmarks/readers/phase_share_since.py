"""Seconds of the program's perfscope phase `phase` inside the window, as a
percentage of the window (host clock, as `phase_share`), for a phase that
an older program under the same yardstick does not have. `since` names a
counter of the program that moves in every window of a program that has
the phase. Where `since` did not move, the program is older than the
phase and spent no time in it: 0.0, so that its traced run still ends with
a result. Where `since` moved and the phase was never entered, the metric
has fallen silent: nothing, which ends the run."""


def read(args: dict, ctx: dict):
    s = ctx["delta"].get("phase." + args["phase"], 0.0)
    if s > 0 and ctx["window_s"]:
        return 100.0 * s / ctx["window_s"]
    return None if ctx["delta"].get(args["since"], 0) else 0.0
