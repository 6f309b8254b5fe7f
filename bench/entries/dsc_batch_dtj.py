"""System under test for a batch cell with DTJ's delta_t refine: the
``dsc_batch`` entry (``repro.core.dsc.run_dsc``, one call a batch), whose
``watch`` also keeps the join's match counts of every batch.
"""
from __future__ import annotations

import contextlib
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_entries_dsc_batch", Path(__file__).with_name("dsc_batch.py"))
dsc_batch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dsc_batch)


class Runner(dsc_batch.Runner):
    @contextlib.contextmanager
    def watch(self, counters: dict):
        """``dsc_batch.Runner.watch``, and under ``counters["join_matches"]``
        each batch's ``DSCOutput.join_matches`` (``[2]`` i32 on the device:
        the join's matches before and after the refine), kept as device
        arrays and read only after the window.  A program without the
        field keeps nothing there."""
        from repro.core import dsc
        matches = counters.setdefault("join_matches", [])
        real = dsc.run_dsc

        def run_dsc(*a, **k):
            out = real(*a, **k)
            m = getattr(out, "join_matches", None)
            if m is not None:
                matches.append(m)
            return out

        with super().watch(counters):
            dsc.run_dsc = run_dsc
            try:
                yield counters
            finally:
                dsc.run_dsc = real
