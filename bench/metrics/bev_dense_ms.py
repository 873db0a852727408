"""Device milliseconds per cloud of the dense BEV stage: the executable
the program launches inside its ``serve.bev`` span (HeightCompression,
the BEV backbone and the anchor head)."""
import phases


def read(ctx):
    s = phases.summary(ctx, __file__)
    t = s.get("label_s", {}).get("serve.bev")
    if not (s.get("paired") and t) or not ctx["clouds"]:
        return None
    return 1e3 * t / ctx["clouds"]
