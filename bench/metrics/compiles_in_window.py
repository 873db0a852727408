"""Backend compiles inside the measured window (should be 0)."""


def read(ctx):
    return ctx["compiles"]
