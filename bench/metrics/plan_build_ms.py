"""Host milliseconds per cloud inside ``minkunet.build_plans``."""


def read(ctx):
    if not ctx["plan_builds"]:
        return None
    return 1e3 * ctx["plan_build_s"] / ctx["plan_builds"]
