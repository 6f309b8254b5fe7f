"""The program's host spans and the per-layer metrics that read them, on
the CPU.

    PYTHONPATH=src python -m pytest -q tests/bench/test_spans.py
"""
from __future__ import annotations

import glob
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import generator  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr_mod  # noqa: E402

MS = 1_000_000                      # ns


def _reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def _trace():
    """A window [0, 100) ms and two batches.  The plannings' spans overlap
    one another and start before the window; a sub-span of a planning and
    the harness's spans are not the program's plannings; the two dispatch
    spans overlap.  Device 0 runs [10, 25) and [30, 60); device 1 runs
    through the window."""
    host = [("bench.window", 0, 100), ("dsc.run", 0, 90),
            ("index.plan.sweep", -10, 20), ("index.boxes", -10, 5),
            ("index.plan.join", 15, 40), ("dispatch._join_sweep", 40, 42),
            ("dispatch._run_dsc_from_join", 41, 45),
            ("index.plan.sweep", 95, 110), ("bench.wait", 45, 100)]
    return tr_mod.Trace(
        ops={0: [("a", 10 * MS, 25 * MS, "m"), ("b", 30 * MS, 60 * MS, "m")],
             1: [("c", 0, 100 * MS, "m")]},
        host=[(n, s * MS, e * MS) for n, s, e in host],
        window=(0, 100 * MS))


def _ctx(tr, n_batches=2):
    return SimpleNamespace(trace=tr, n_batches=n_batches, chips=len(tr.ops))


def test_plan_host_ms_is_the_union_of_the_plannings_in_the_window():
    # [0, 40) from the overlapping sweep and join, [95, 100) clipped
    assert _reader("index.plan_host_ms").read(_ctx(_trace())) == \
        pytest.approx(45 / 2)


def test_plan_idle_ms_is_device_idle_under_the_plannings():
    # device 0 idles [0, 10), [25, 30), [60, 100): 10 + 5 + 5 under the
    # plannings; device 1 never idles; the mean over chips per batch
    assert _reader("index.plan_idle_ms").read(_ctx(_trace())) == \
        pytest.approx((20 + 0) / 2 / 2)


def test_dispatch_host_ms_is_the_union_of_the_dispatch_spans():
    assert _reader("dispatch.host_ms").read(_ctx(_trace())) == \
        pytest.approx(5 / 2)


@pytest.mark.parametrize("name", ["index.plan_host_ms", "index.plan_idle_ms",
                                  "dispatch.host_ms"])
def test_span_readers_without_spans_read_none(name):
    tr = _trace()
    tr.host = [(n, s, e) for n, s, e in tr.host
               if n.startswith(("bench.", "dsc.", "index.boxes"))]
    assert _reader(name).read(_ctx(tr)) is None
    # spans that lie wholly outside the window count as none
    tr.host.append(("index.plan.join", 120 * MS, 130 * MS))
    tr.host.append(("dispatch._join_sweep", 120 * MS, 130 * MS))
    assert _reader(name).read(_ctx(tr)) is None


def test_plan_idle_overlap_of_interval_lists():
    ov = _reader("index.plan_idle_ms").overlap_ns
    assert ov([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert ov([(0, 10)], [(10, 20)]) == 0
    assert ov([], [(0, 5)]) == 0


# --------------------------------------------------------------------------
# the program's own spans, from a profiler trace of run_dsc
# --------------------------------------------------------------------------

STEPS = ("index.boxes", "index.grid", "index.mask", "index.compact")


@pytest.fixture(scope="module")
def traced_runs(tmp_path_factory):
    """Two ``run_dsc`` calls on one 64-track batch of the ``day`` traffic
    under ``brest_ais``'s plan (interpret mode on the CPU, small tiles),
    traced after a warm-up call; the host events of the calling thread,
    and the batch, parameters and plan."""
    import jax
    from jax.profiler import ProfileData

    from repro.core.dsc import run_dsc
    from repro.core.plan import EnginePlan

    entry = run.load_module(BENCH / "entries" / "dsc_batch.py")
    cfg = json.loads((BENCH / "configs" / "brest_ais.json").read_text())
    cfg = {**cfg, "n_trajs": 64, "max_points": 16, "w": 4,
           "plan": {**cfg["plan"], "cluster_bu": 8, "cluster_bs": 128,
                    "fused_rows": 4, "fused_bc": 8, "fused_bm": 16}}
    mix = {**generator.load_mix(BENCH / "traffic" / "day.json"),
           "fixes_min": 8, "fixes_max": 16, "pool": 1}
    batch = generator.make_pool(2**31 + 11, cfg, mix)[0]
    runner = entry.Runner(cfg, jax.devices())
    params, tb = runner.params(batch), runner.to_device(batch)
    plan = EnginePlan(**cfg["plan"])
    jax.block_until_ready(run_dsc(tb, params, plan=plan).result)
    out_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out_dir))
    try:
        for _ in range(2):
            jax.block_until_ready(run_dsc(tb, params, plan=plan).result)
    finally:
        jax.profiler.stop_trace()
    f, = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                   recursive=True)
    host = next(p for p in ProfileData.from_file(f).planes
                if p.name == "/host:CPU")
    line = next(ln for ln in host.lines
                if any(ev.name == "dsc.run" for ev in ln.events))
    ours = ("dsc.", "index.", "dispatch.")
    events = sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      dict(ev.stats) if ev.name.startswith(ours) else {})
                     for ev in line.events),
                    key=lambda ev: (ev[1], -ev[2]))
    return events, tb, params, plan


def _within(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_run_dsc_spans_nest_in_order(traced_runs):
    events, tb, _, plan = traced_runs
    runs = [ev for ev in events if ev[0] == "dsc.run"]
    assert len(runs) == 2                       # one a call
    for r in runs:
        assert r[3]["T"] == 64 and r[3]["M"] == 16
        assert r[3]["mode"] == plan.mode and r[3]["attempts"] == 1
        inner = [ev for ev in events if _within(ev, r)
                 and ev[0].startswith(("index.plan.", "dispatch."))]
        assert [ev[0] for ev in inner] == [
            "index.plan.sweep", "index.plan.join", "dispatch._join_sweep",
            "dispatch._run_dsc_from_join"]
        for planning in inner[:2]:
            steps = [ev[0] for ev in events if _within(ev, planning)
                     and ev[0] in STEPS]
            assert steps == list(STEPS)


def test_planning_span_counters_match_the_plans(traced_runs):
    from repro.kernels.stjoin import ops
    events, tb, params, plan = traced_runs
    tp = ops.plan_fused_tiles(
        tb.x, tb.y, tb.t, tb.valid, tb.x, tb.y, tb.t, tb.valid,
        params.eps_sp, params.eps_t, rows=plan.fused_rows, bc=plan.fused_bc,
        bm=plan.fused_bm)
    ids = np.asarray(tp.tile_ids)
    n_cand = -(-tb.x.shape[0] // tp.bc)
    want_sweep = {"kept": int((ids >= 0).sum()),
                  "total": ids.shape[0] * n_cand, "K": ids.shape[1],
                  "rows": tp.rows, "bc": tp.bc}
    mask, counts, _ = ops.plan_join_index(
        tb, tb, params.eps_sp, params.eps_t, bc=min(128, tb.x.shape[0]))
    want_join = {"kept": int(np.asarray(mask).sum()), "total": mask.size,
                 "K": max(int(np.asarray(counts).max()), 1)}
    assert 0 < want_sweep["kept"] < want_sweep["total"]
    for name, want in (("index.plan.sweep", want_sweep),
                       ("index.plan.join", want_join)):
        got = [ev[3] for ev in events if ev[0] == name]
        assert len(got) == 2
        for stats in got:
            assert {k: stats[k] for k in want} == want


def test_span_readers_read_the_programs_spans(traced_runs):
    """The readers find the program's spans in a trace of it: with no
    device operations in the trace, the device idles under all of the
    plannings."""
    events, *_ = traced_runs
    runs = [ev for ev in events if ev[0] == "dsc.run"]
    tr = tr_mod.Trace(ops={0: []}, host=[ev[:3] for ev in events],
                      window=(runs[0][1], runs[-1][2]))
    host_ms = _reader("index.plan_host_ms").read(_ctx(tr))
    assert host_ms > 0
    assert _reader("index.plan_idle_ms").read(_ctx(tr)) == \
        pytest.approx(host_ms)
    assert 0 < _reader("dispatch.host_ms").read(_ctx(tr)) < \
        sum(e - s for _, s, e, _ in runs) / 1e6 / 2
