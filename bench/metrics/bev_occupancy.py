"""Active cells entering the BEV map per cloud, in percent of the map's
H x W cells: the program's ``serve.bev_sites`` counter (the last sparse
resolution's output sites, one per cell and height plane, so up to
twice the cells where two planes remain) over the clouds served.

Both counts run from process start, so the warm-up tick's clouds are in
both; every cloud of the mix is fresh geometry, so the ratio is the
window's."""
from repro.runtime import guard


def read(ctx):
    sites = guard.health().get("serve.bev_sites")
    clouds = guard.health().get("serve.completed")
    cells = ctx["work"].get("bev_cells")
    if not sites or not clouds or not cells or not ctx["clouds"]:
        return None
    return 100.0 * (sites / clouds) / (cells / ctx["clouds"])
