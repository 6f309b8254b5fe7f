"""Share of the traced window in which the device ran nothing, in %:
1 - (union of its operations' intervals) / window, the mean over the
cell's chips."""
import trace_reduce


def read(ctx):
    tr = ctx.trace
    shares = [1.0 - trace_reduce.busy_ns(tr, d) / tr.window_ns
              for d in tr.ops]
    return 100.0 * sum(shares) / len(shares)
