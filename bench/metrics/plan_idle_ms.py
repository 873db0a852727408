"""Device idle milliseconds per cloud whose gap has an innermost program
span named ``plan.*`` open at its middle: the chip waiting on the host's
plan build."""
import phases


def read(ctx):
    s = phases.summary(ctx, __file__)
    if not s.get("spanned") or not ctx["clouds"]:
        return None
    return 1e3 * s["plan_idle_s"] / ctx["clouds"]
