"""Device time of TSA2's windowed-Jaccard kernel (the Pallas kernel
``jaccard_pallas``, matched by name) per batch, in ms, on the busiest
chip."""
import trace_reduce

OPS = r"jaccard_pallas"


def device_ms(ctx):
    tr = ctx.trace
    ns = max(trace_reduce.length(trace_reduce.matching(tr, d, OPS))
             for d in tr.ops)
    return ns / 1e6 / ctx.n_batches if ns else None


read = device_ms
