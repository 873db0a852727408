"""Device milliseconds per cloud of the tile build: the executables the
program launches inside its ``plan.tiles`` span (``build_tap_tiles``)."""
import phases


def read(ctx):
    return phases.per_cloud_ms(ctx, __file__, "tiles_s")
