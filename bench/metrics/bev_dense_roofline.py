"""Share of its roofline that the dense BEV stage reaches, in percent.

The least time the chip could take over the window's clouds, the sum of
``bev_min_s`` (the family's counts: the stage's FLOPs over the bf16 peak
or its bytes over the HBM peak, the larger), over the device time of
the executable launched inside the program's ``serve.bev`` span.
"""
import phases


def read(ctx):
    s = phases.summary(ctx, __file__)
    t = s.get("label_s", {}).get("serve.bev")
    least = ctx["work"].get("bev_min_s")
    if not (s.get("paired") and t and least):
        return None
    return 100.0 * least / t
