"""Share of the join's matches that DTJ's delta_t refine keeps, in %: the
matches after the refine over the matches before it, summed over the
window's batches, from the counts the program returns with each batch
(``DSCOutput.join_matches``, kept by the entry's ``watch``).  None where
the program returns no counts."""
import numpy as np


def read(ctx):
    counts = ctx.counters.get("join_matches")
    if not counts:
        return None
    before, after = np.sum([np.asarray(c, np.int64) for c in counts], axis=0)
    return 100.0 * after / before if before else None
