"""Tests of the benchmark harness (``bench/``), on the CPU.

    PYTHONPATH=src python -m pytest -q tests/bench
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
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
import peaks  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr_mod  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _trace():
    """Two devices over a window [0, 100): device 0 runs a join op in
    [10, 30), a sim op in [25, 40), a collective in [50, 70) that a
    compute op overlaps in [60, 65), and one op after the window; device 1
    runs one op over the whole window and past it."""
    return tr_mod.Trace(
        ops={0: [("join_fusion", 10, 30, "jit__join_sweep"),
                 ("%stjoin_sim_fused_flat.1", 25, 40, "jit__run_dsc_from_join"),
                 ("all-gather.1", 50, 70, "jit_body"),
                 ("fusion.3", 60, 65, "jit_body"),
                 ("late", 120, 130, "jit_body")],
             1: [("fusion.9", -5, 105, "jit_body")]},
        host=[("bench.window", 0, 100), ("bench.entry", 0, 45),
              ("bench.wait", 45, 100), ("PjitFunction(f)", 40, 48)],
        window=(0, 100))


def test_trace_union_busy_and_idle():
    tr = _trace()
    assert tr_mod.busy_ns(tr, 0) == 30 + 20           # [10,40) + [50,70)
    assert tr_mod.busy_ns(tr, 1) == 100               # clipped to window
    assert tr_mod.idle_gaps(tr, 0) == [(0, 10), (40, 50), (70, 100)]
    assert tr_mod.idle_gaps(tr, 1) == []


def test_trace_per_kernel_time():
    tr = _trace()
    assert tr_mod.length(tr_mod.matching(tr, 0, r"stjoin_sim_fused")) == 15
    assert tr_mod.length(
        tr_mod.matching(tr, 0, r"_join_sweep", by="module")) == 20
    assert tr_mod.length(tr_mod.matching(tr, 0, r"late")) == 0


def test_trace_breakdown_labels_gaps_by_host_span():
    bd = tr_mod.breakdown(_trace())
    ops = dict(bd["device_ops"])
    assert ops["fusion.9"] == pytest.approx(100e-9)
    assert ops["%stjoin_sim_fused_flat.1"] == pytest.approx(15e-9)
    gaps = dict(bd["idle_gaps"])
    # [0,10) in the entry span; [40,50) goes to JAX's dispatch span,
    # which covers 8 of its 10 ns; [70,100) while the host waits
    assert gaps == pytest.approx({"bench.entry": 10e-9,
                                  "PjitFunction(f)": 10e-9,
                                  "bench.wait": 30e-9})


def test_trace_metric_readers():
    ctx = SimpleNamespace(trace=_trace(), n_batches=1, compiles=0, chips=2,
                          cfg={"n_trajs": 4, "max_points": 2},
                          peaks=peaks.peaks_for("TPU v5 lite"))
    idle = run.load_module(BENCH / "metrics" / "device.idle_share.py")
    assert idle.read(ctx) == pytest.approx(100 * (0.5 + 0.0) / 2)
    sim = run.load_module(BENCH / "metrics" / "similarity.device_ms.py")
    assert sim.read(ctx) == pytest.approx(15e-6)
    join = run.load_module(BENCH / "metrics" / "join.device_ms.py")
    assert join.read(ctx) == pytest.approx(20e-6)


def test_join_bytes_hand_count():
    roof = run.load_module(BENCH / "metrics" / "join_roofline.py")
    # T=2 tracks of M=3 points, C=2 candidates: 6 points x (x, y, t f32 +
    # valid 1 B) = 78, ids 2 x 4 = 8, cube 2*3*2 cells x (w f32 + idx i32)
    assert roof.join_bytes(2, 3, 2) == 78 + 8 + 96
    assert roof.join_bytes(2048, 128, 2048) == (
        2048 * 128 * 13 + 2048 * 4 + 2048 * 128 * 2048 * 8)


def test_peaks_table_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def _cfg(**kw):
    cfg = json.loads((BENCH / "configs" / "brest_ais.json").read_text())
    return {**cfg, **kw}


def _mix():
    return generator.load_mix(BENCH / "traffic" / "day.json")


def test_day_is_deterministic_per_seed():
    mix, cfg = _mix(), _cfg(n_trajs=16)
    a = generator.make_pool(2**31 + 5, cfg, mix)
    b = generator.make_pool(2**31 + 5, cfg, mix)
    c = generator.make_pool(2**31 + 6, cfg, mix)
    assert len(a) == mix["pool"]
    for x, y in zip(a, b):
        for f in ("x", "y", "t", "valid", "traj_id"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
        assert (x.eps_sp, x.eps_t) == (y.eps_sp, y.eps_t)
    assert not np.array_equal(a[0].x, c[0].x)
    assert not np.array_equal(a[0].x, a[1].x)


def test_day_keeps_the_source_rates():
    """Fixes per track average the source's 17e6 / 3.65e5; first fixes
    spread over the batch's share of a day at 3.65e5 / 183 tracks a day,
    rows in first-fix order; the sampling interval is the mix's."""
    mix, cfg = _mix(), _cfg(n_trajs=1024)
    b = generator.make_pool(7, cfg, {**mix, "pool": 1})[0]
    n = b.valid.sum(1)
    assert n.min() >= mix["fixes_min"] and n.max() <= mix["fixes_max"]
    assert n.mean() == pytest.approx(17e6 / 3.65e5, rel=0.03)
    first = b.t[:, 0]
    assert np.all(np.diff(first) >= 0)
    span = 86_400 * 1024 / (3.65e5 / 183)
    assert first.max() == pytest.approx(span, rel=0.02)
    assert b.eps_t == pytest.approx(mix["sample_dt"], rel=0.02)
    # a track covers about one lane: its fixes do not pile up at an end
    steps = np.hypot(np.diff(b.x, axis=1), np.diff(b.y, axis=1))
    moving = (steps > 0.1 * mix["lane_width"]) & b.valid[:, 1:]
    assert moving.sum() > 0.8 * (b.valid[:, 1:]).sum()


def test_fixes_max_beyond_the_row_width_is_refused():
    with pytest.raises(ValueError, match="fixes_max"):
        generator.make_pool(1, _cfg(n_trajs=4, max_points=32), _mix())


def test_kept_tile_shares_count_the_programs_plans():
    """The entry's watch keeps one tile plan per planning and batch, and
    the readers turn them into shares of all tiles."""
    import jax
    entry = run.load_module(BENCH / "entries" / "dsc_batch.py")
    mix = {**_mix(), "fixes_min": 8, "fixes_max": 16, "pool": 2}
    cfg = _cfg(n_trajs=64, max_points=16, w=4,
               plan={**_cfg()["plan"], "cluster_bu": 8, "cluster_bs": 128})
    runner = entry.Runner(cfg, jax.devices())
    counters = {}
    with runner.watch(counters):
        for b in generator.make_pool(3, cfg, mix):
            runner.run(b, runner.params(b))
    assert [len(counters[k]) for k in ("join_tiles", "sweep_tiles")] == [2, 2]
    from repro.kernels.stjoin import ops
    assert ops.plan_fused_tiles.__name__ == "plan_fused_tiles"
    assert ops.plan_fused_tiles.__module__ == ops.__name__
    ctx = SimpleNamespace(counters=counters)
    for name in ("index.join_kept_tile_share", "index.sweep_kept_tile_share"):
        share = run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
        assert 0.0 < share <= 100.0
    assert run.load_module(BENCH / "metrics" / "index.join_kept_tile_share.py"
                           ).read(SimpleNamespace(counters={})) is None


def test_kept_tile_share_readers_by_hand():
    join = run.load_module(BENCH / "metrics" / "index.join_kept_tile_share.py")
    sweep = run.load_module(BENCH / "metrics"
                            / "index.sweep_kept_tile_share.py")
    mask = np.array([[True, False], [True, True]])
    ids = np.array([[0, -1], [1, 0]])
    ctx = SimpleNamespace(counters={"join_tiles": [(mask, 4), (mask, 4)],
                                    "sweep_tiles": [(ids, 6)]})
    assert join.read(ctx) == pytest.approx(75.0)
    assert sweep.read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("cell", CELLS)
def test_cells_resolve_to_files(cell):
    bench, wl, cfg, mix = run.load_cell(cell)
    assert (BENCH / "entries" / f"{cfg['entry']}.py").is_file()
    assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    assert set(cfg["limits"]) == {"slots_mismatched", "member_sim_gap"}
    for m in bench["per_layer"]:
        if cell in m.get("workloads", [cell]):
            mod = run.load_module(BENCH / "metrics" / f"{m['name']}.py")
            assert callable(mod.read)
    names = {m["name"] for m in bench["end_to_end"]}
    assert names == {"points_per_s", "setup_s"}


def test_benchmark_json_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        file_cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(file_cfg["reduced"])
    assert all(not Path(p).is_absolute() and ".." not in p
               for p in b["paths"])
    for w in b["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    moves = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in moves
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def _bench_cmd(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_tpu_fails_and_prints_no_result():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
