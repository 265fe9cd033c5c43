"""Compilations the program's compile listener counted inside the window,
over every kernel (or only `kernel`). Should read 0."""


def read(args: dict, ctx: dict):
    want = "compiles." + args["kernel"] if "kernel" in args else "compiles."
    return sum(v for k, v in ctx["delta"].items() if k.startswith(want))
