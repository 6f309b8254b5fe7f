"""Device time of the join program (the index-pruned Pallas join sweep,
``_join_sweep``) per batch, in ms, on the busiest chip."""
import trace_reduce

MODULE = r"_join_sweep"


def device_ms(ctx):
    tr = ctx.trace
    per_dev = [trace_reduce.length(trace_reduce.matching(tr, d, MODULE,
                                                         by="module"))
               for d in tr.ops]
    ns = max(per_dev)
    return ns / 1e6 / ctx.n_batches if ns else None


read = device_ms
