"""Device time of the clustering rounds (the Pallas round-scan and
claim-max kernels, matched by name) per batch, in ms, on the busiest chip."""
import trace_reduce

OPS = r"round_scan_pallas|assign_pallas"


def read(ctx):
    tr = ctx.trace
    ns = max(trace_reduce.length(trace_reduce.matching(tr, d, OPS))
             for d in tr.ops)
    return ns / 1e6 / ctx.n_batches if ns else None
