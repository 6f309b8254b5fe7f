"""Host time of the program's two index plannings per batch, in ms: the
length of the union of its ``index.plan.*`` spans (``index.plan.join``
and ``index.plan.sweep``, written by ``repro.kernels.stjoin.ops``) within
the window, over the window's batches.  None where the program writes no
such span."""
import trace_reduce

PREFIX = "index.plan."


def read(ctx):
    tr = ctx.trace
    spans = trace_reduce.clip([(s, e) for n, s, e in tr.host
                               if n.startswith(PREFIX)], *tr.window)
    return trace_reduce.length(spans) / 1e6 / ctx.n_batches if spans else None
