"""Device milliseconds per cloud of the map search: the executables the
program launches inside its ``plan.search`` spans, the ``octent_query``
kernel among them."""
import phases


def read(ctx):
    return phases.per_cloud_ms(ctx, __file__, "search_s")
