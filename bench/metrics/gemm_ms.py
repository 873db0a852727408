"""Device milliseconds per cloud in the ``spconv_gemm_fused`` kernel."""


def read(ctx):
    t = ctx["trace"].get("kernel_s", {}).get("spconv_gemm_fused")
    if not t or not ctx["clouds"]:
        return None
    return 1e3 * t / ctx["clouds"]
