"""Map searches (``plan.MAPSEARCH_CALLS``) per completed cloud."""


def read(ctx):
    if not ctx["clouds"]:
        return None
    return ctx["mapsearch_calls"] / ctx["clouds"]
