"""Device milliseconds per cloud in the ``octent_query`` kernel."""


def read(ctx):
    t = ctx["trace"].get("kernel_s", {}).get("octent_query")
    if not t or not ctx["clouds"]:
        return None
    return 1e3 * t / ctx["clouds"]
