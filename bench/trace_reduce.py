"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain intervals on one clock (nanoseconds):

* ``ops[device]``: ``(name, start, end, module)`` for every operation that
  ran on that device, ``module`` the XLA program it ran in;
* ``host``: ``(name, start, end)`` for the host spans of the benchmark's
  own thread (its ``bench.*`` annotations and JAX's own, nested);
* ``window``: ``(start, end)`` of the ``bench.window`` span.

``load`` builds that from the ``.xplane.pb`` file JAX's profiler writes;
the tests build it by hand.  Everything else here is interval arithmetic.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)    # device -> [(name, s, e, mod)]
    host: list = field(default_factory=list)   # [(name, s, e)]
    window: tuple = (0, 0)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def _device_id(plane_name: str):
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    tr = Trace()
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            ops = []
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s = ev.start_ns
                ops.append((op_name(ev.name), s, s + ev.duration_ns,
                            _enclosing(mods, s)))
            tr.ops[dev] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    tr.host = evs
                    tr.window = next((s, e) for n, s, e in evs
                                     if n == WINDOW_SPAN)
    if not tr.ops:
        raise ValueError(f"{trace_dir}: the trace holds no TPU device plane")
    if tr.window == (0, 0):
        raise ValueError(f"{trace_dir}: no {WINDOW_SPAN} host span")
    return tr


def op_name(hlo: str) -> str:
    """``%stjoin_pallas.1`` from the trace's ``%stjoin_pallas.1 = (...)
    custom-call(...)``: an operation's name without its HLO text."""
    return hlo.split(" = ", 1)[0]


def _enclosing(mods, t):
    """Name of the module span that contains time ``t`` ('' if none)."""
    lo, hi = 0, len(mods)
    while lo < hi:                      # last module starting at or before t
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t < mods[lo - 1][1]:
        return mods[lo - 1][2]
    return ""


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def busy_ns(tr: Trace, dev) -> int:
    """Time within the window in which any operation ran on ``dev``."""
    return length(clip([(s, e) for _, s, e, _ in tr.ops[dev]], *tr.window))


def matching(tr: Trace, dev, pattern: str, *, by: str = "name"):
    """Intervals of ``dev``'s operations in the window whose name (or, with
    ``by="module"``, whose program's name) matches ``pattern``."""
    rx = re.compile(pattern)
    return clip([(s, e) for n, s, e, m in tr.ops[dev]
                 if rx.search(m if by == "module" else n)], *tr.window)


def idle_gaps(tr: Trace, dev):
    """``(start, end)`` of the window's stretches in which ``dev`` ran
    nothing."""
    gaps, t = [], tr.window[0]
    for s, e in union(clip([(s, e) for _, s, e, _ in tr.ops[dev]],
                           *tr.window)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if tr.window[1] > t:
        gaps.append((t, tr.window[1]))
    return gaps


def host_labels(tr: Trace, gaps):
    """For each of the sorted, disjoint ``gaps``, the host span that covers
    most of it (the innermost on a tie), or ``host idle`` where none
    overlaps.  One sweep over the host spans in order of start."""
    spans = sorted((s, e, n) for n, s, e in tr.host if n != WINDOW_SPAN)
    out, active, i = [], [], 0
    for gs, ge in gaps:
        while i < len(spans) and spans[i][0] < ge:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > gs]
        best, key = "host idle", (0, 0)
        for hs, he, n in active:
            ov = min(ge, he) - max(gs, hs)
            if ov > 0 and (ov, -(he - hs)) > key:
                best, key = n, (ov, -(he - hs))
        out.append(best)
    return out


def breakdown(tr: Trace, n: int = 10) -> dict:
    """Top device operations by time (summed over devices, seconds), and
    idle time on the devices summed by what the host was doing."""
    by_op, by_host = {}, {}
    for dev, ops in tr.ops.items():
        for name, s, e in ((nm, s, e) for nm, s, e, _ in ops):
            d = min(e, tr.window[1]) - max(s, tr.window[0])
            if d > 0:
                by_op[name] = by_op.get(name, 0) + d
        gaps = idle_gaps(tr, dev)
        for (s, e), lab in zip(gaps, host_labels(tr, gaps)):
            by_host[lab] = by_host.get(lab, 0) + (e - s)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    return {"device_ops": top(by_op), "idle_gaps": top(by_host)}
