"""Share of the row tiles in the grid of the served expert layers'
grouped products that are computed: ``moe_live_tiles / moe_grid_tiles``
from ``ServingMetrics`` over the window.  The grid is laid for the worst
case (every pair of a tick on one expert, a tile more for each expert);
a tile past the last expert's is a grid step that moves and computes
nothing.  ``None`` where the program has no such counters."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("moe_grid_tiles"):
        return None
    return 100.0 * c["moe_live_tiles"] / c["moe_grid_tiles"]
