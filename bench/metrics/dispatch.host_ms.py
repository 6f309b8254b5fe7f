"""Host time spent enqueueing the program's jitted stage programs per
batch, in ms: the length of the union of its ``dispatch.*`` spans
(``repro.utils.programs.run_program``) within the window, over the
window's batches.  None where the program writes no such span."""
import trace_reduce

PREFIX = "dispatch."


def read(ctx):
    tr = ctx.trace
    spans = trace_reduce.clip([(s, e) for n, s, e in tr.host
                               if n.startswith(PREFIX)], *tr.window)
    return trace_reduce.length(spans) / 1e6 / ctx.n_batches if spans else None
