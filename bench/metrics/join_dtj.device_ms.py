"""Device time of the join with DTJ's delta_t refine in the kernel (the
Pallas kernel ``stjoin_dtj_pallas``, matched by name; the plain join's
kernel is ``stjoin_pallas``) per batch, in ms, on the busiest chip."""
import trace_reduce

OPS = r"stjoin_dtj_pallas"


def device_ms(ctx):
    tr = ctx.trace
    ns = max(trace_reduce.length(trace_reduce.matching(tr, d, OPS))
             for d in tr.ops)
    return ns / 1e6 / ctx.n_batches if ns else None


read = device_ms
