"""Host milliseconds per cloud inside the program's plan build: the entry
that the family's ``serve`` names as ``plan_build``."""


def read(ctx):
    if not ctx["plan_builds"]:
        return None
    return 1e3 * ctx["plan_build_s"] / ctx["plan_builds"]
