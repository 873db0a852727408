"""Model FLOPs served per second over the chip's bf16 peak, in percent:
the forward's FLOPs per cloud (convs and head, counts.py) times the
window's clouds over the window's seconds."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["work"]["model_flops"]:
        return None
    rate = ctx["work"]["model_flops"] / ctx["window_s"]
    return 100.0 * rate / ctx["peaks"]["flops_per_s"]
