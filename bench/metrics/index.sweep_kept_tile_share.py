"""Share of the candidate tiles that the grid index keeps for the
similarity sweep, in %: the tile plans ``run_dsc`` made in the window
(``plan_fused_tiles``, through ``plan_sweep_tiles``), kept tiles over all
tiles, summed over the window's batches."""
import numpy as np


def read(ctx):
    plans = ctx.counters.get("sweep_tiles")
    if not plans:
        return None
    kept = sum(int((np.asarray(ids) >= 0).sum()) for ids, _ in plans)
    return 100.0 * kept / sum(total for _, total in plans)
