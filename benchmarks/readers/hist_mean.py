"""Mean of the histogram `hist` over the window (delta of its sum over
delta of its count), times `scale`; nothing where it recorded nothing."""


def read(args: dict, ctx: dict):
    n = ctx["delta"].get(args["hist"] + "_count", 0)
    if not n:
        return None
    return args.get("scale", 1.0) * ctx["delta"].get(
        args["hist"] + "_sum", 0.0) / n
