"""Share of the candidate tiles that the grid index keeps for the Pallas
join, in %: the tile masks ``run_dsc``'s join planned in the window
(``plan_join_index``), kept tiles over all tiles, summed over the
window's batches."""
import numpy as np


def read(ctx):
    plans = ctx.counters.get("join_tiles")
    if not plans:
        return None
    kept = sum(int(np.asarray(mask).sum()) for mask, _ in plans)
    return 100.0 * kept / sum(total for _, total in plans)
