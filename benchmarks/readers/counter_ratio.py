"""`scale` x delta of counter `num` / delta of counter `den` over the
window; nothing where the denominator did not move."""


def read(args: dict, ctx: dict):
    den = ctx["delta"].get(args["den"], 0)
    if not den:
        return None
    return args.get("scale", 1.0) * ctx["delta"].get(args["num"], 0) / den
