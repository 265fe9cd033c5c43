"""`scale` x delta of counter `num` / delta of counter `den` over the
window (as `counter_ratio`), for counters that an older program under the
same yardstick does not have. `since` names a counter of the program that
moves in every window of a program that has them. Where `since` did not
move, the program is older than the counters: 0.0, meaning "absent", so
that its traced run still ends with a result. Where `since` moved and the
denominator did not, the metric has fallen silent: nothing, which ends the
run."""


def read(args: dict, ctx: dict):
    den = ctx["delta"].get(args["den"], 0)
    if den:
        return args.get("scale", 1.0) * ctx["delta"].get(args["num"], 0) / den
    return None if ctx["delta"].get(args["since"], 0) else 0.0
