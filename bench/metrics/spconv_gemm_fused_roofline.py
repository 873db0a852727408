"""Share of its roofline that the fused GEMM reaches, in percent.

The least time the chip could take over the window's convs, the sum of
``max(flops / peak FLOP/s, bytes / peak B/s)`` per conv from the
geometry (counts.py), over the kernel's summed device time.
"""


def read(ctx):
    t = ctx["trace"].get("kernel_s", {}).get("spconv_gemm_fused")
    if not t or not ctx["work"]["conv_min_s"]:
        return None
    return 100.0 * ctx["work"]["conv_min_s"] / t
