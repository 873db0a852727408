"""Device milliseconds per cloud of the forward's XLA ops: the forward
executable, launched inside the program's ``serve.dispatch`` span, less
the ``spconv_gemm_fused`` kernel (BN/ReLU, concat, head, liveness
sweeps, feature relayout)."""
import phases


def read(ctx):
    return phases.per_cloud_ms(ctx, __file__, "forward_xla_s")
