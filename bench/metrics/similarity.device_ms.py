"""Device time of the similarity sweep (the Pallas pass-2 sweep kernels,
matched by name) per batch, in ms, on the busiest chip."""
import trace_reduce

OPS = r"stjoin_sim_fused_flat|stjoin_sim_panel_flat"


def read(ctx):
    tr = ctx.trace
    ns = max(trace_reduce.length(trace_reduce.matching(tr, d, OPS))
             for d in tr.ops)
    return ns / 1e6 / ctx.n_batches if ns else None
