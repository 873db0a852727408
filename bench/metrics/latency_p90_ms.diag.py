"""90th percentile of request latency (submit to logits on the host),
read in the traced run, where too few requests complete for a judged
tail."""
import numpy as np


def read(ctx):
    if not ctx["latency_ms"]:
        return None
    return float(np.percentile(ctx["latency_ms"], 90))
