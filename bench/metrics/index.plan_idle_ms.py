"""Device idle time under the program's index plannings per batch, in ms:
the length of the device's idle gaps in the window intersected with the
union of the ``index.plan.*`` host spans, over the window's batches, the
mean over the cell's chips.  At most ``index.plan_host_ms``.  None where
the program writes no such span."""
import trace_reduce

PREFIX = "index.plan."


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    n = i = j = 0
    while i < len(a) and j < len(b):
        n += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def read(ctx):
    tr = ctx.trace
    spans = trace_reduce.union(trace_reduce.clip(
        [(s, e) for n, s, e in tr.host if n.startswith(PREFIX)], *tr.window))
    if not spans:
        return None
    per_dev = [overlap_ns(trace_reduce.idle_gaps(tr, d), spans)
               for d in tr.ops]
    return sum(per_dev) / len(per_dev) / 1e6 / ctx.n_batches
