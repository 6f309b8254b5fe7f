"""Record the compiled programs a host-driven dispatcher runs.

The dispatchers that plan on the host and then call jitted stage programs
(``repro.core.dsc.run_dsc``, the pruned Pallas join) call them through
:func:`run_program`.  Inside :func:`record_programs` each such call is
also lowered with the exact arguments it ran with, so a caller can
inspect the programs that ran (for example, count the Pallas kernels in
their compiled text) without rebuilding them.  Outside the block the
call is made directly and nothing is lowered.
"""
from __future__ import annotations

import contextlib

_recorders: list[list] = []


def run_program(fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` for a jitted ``fn``, recorded as
    ``(fn.__name__, fn.lower(*args, **kwargs))`` by every open
    :func:`record_programs` block."""
    if _recorders:
        lowered = fn.lower(*args, **kwargs)
        for got in _recorders:
            got.append((fn.__name__, lowered))
    return fn(*args, **kwargs)


@contextlib.contextmanager
def record_programs():
    """Yield a list that collects ``(name, jax.stages.Lowered)`` for every
    :func:`run_program` call made inside the block, in call order."""
    got: list = []
    _recorders.append(got)
    try:
        yield got
    finally:
        _recorders.remove(got)
