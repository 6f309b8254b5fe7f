"""The per-layer metrics of the cell ``brest_day_tsa2_dt`` on hand-built
traces and counters, and the entry's watch that keeps the join's match
counts, on the CPU.

    PYTHONPATH=src python -m pytest -q tests/bench/test_dtj_metrics.py
"""
from __future__ import annotations

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

MS = 1_000_000                      # ns
NEW = ["join_dtj.device_ms", "join_dtj_roofline", "tsa2.device_ms",
       "tsa2_roofline", "join.dtj_kept_match_share"]


def _reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py")


def _trace():
    """A window [0, 100) ms.  Device 0 runs the refined join [10, 30) and
    [35, 45), the plain join [50, 60), the Jaccard kernel [60, 62) and one
    refined join after the window; device 1 runs the refined join for
    5 ms and the Jaccard kernel for 3."""
    return tr_mod.Trace(
        ops={0: [("%stjoin_dtj_pallas.1", 10 * MS, 30 * MS, "jit__join_sweep"),
                 ("%stjoin_dtj_pallas.1", 35 * MS, 45 * MS, "jit__join_sweep"),
                 ("%stjoin_pallas.1", 50 * MS, 60 * MS, "jit__join_sweep"),
                 ("%jaccard_pallas.1", 60 * MS, 62 * MS, "jit_run"),
                 ("%stjoin_dtj_pallas.1", 120 * MS, 130 * MS, "j")],
             1: [("%stjoin_dtj_pallas.2", 0, 5 * MS, "jit__join_sweep"),
                 ("%jaccard_pallas.2", 5 * MS, 8 * MS, "jit_run")]},
        host=[("bench.window", 0, 100 * MS)], window=(0, 100 * MS))


def _ctx(tr=None, counters=None, n_batches=2, T=2048, M=128):
    return SimpleNamespace(trace=tr or _trace(), n_batches=n_batches,
                           counters=counters or {}, chips=1,
                           cfg={"n_trajs": T, "max_points": M},
                           peaks=peaks.peaks_for("TPU v5 lite"))


def test_join_dtj_device_ms_reads_the_refined_kernel_only():
    # 30 ms of the refined kernel on device 0 over 2 batches; the plain
    # kernel and what ran after the window do not count
    assert _reader("join_dtj.device_ms").read(_ctx()) == pytest.approx(15.0)


def test_tsa2_device_ms_reads_the_jaccard_kernel_on_the_busiest_chip():
    assert _reader("tsa2.device_ms").read(_ctx()) == pytest.approx(1.5)


def test_join_dtj_roofline_is_the_joins_bytes_over_its_time():
    roof = _reader("join_roofline")
    want = 100 * roof.join_bytes(2048, 128, 2048) / 819e9 / 15e-3
    assert _reader("join_dtj_roofline").read(_ctx()) == pytest.approx(want)
    # 4.3 GB at 819 GB/s is 5.2 ms, a third of 15 ms
    assert want == pytest.approx(35.0, abs=0.1)


def test_tsa2_bytes_hand_count():
    roof = _reader("tsa2_roofline")
    # T=2 tracks of M=3 fixes, C=40 candidates -> 2 words a fix: 6 fixes
    # x 2 words x 4 B = 48, d 6 x 4 B (no validity: the kernel takes
    # only the words)
    assert roof.tsa2_bytes(2, 3, 40) == 48 + 24
    assert roof.tsa2_bytes(2048, 128, 2048) == (
        2048 * 128 * 64 * 4 + 2048 * 128 * 4)


def test_tsa2_roofline_is_the_kernels_bytes_over_its_time():
    roof = _reader("tsa2_roofline")
    want = 100 * roof.tsa2_bytes(2048, 128, 2048) / 819e9 / 1.5e-3
    assert roof.read(_ctx()) == pytest.approx(want)
    assert 0 < want < 100


def test_kept_match_share_sums_the_counts_over_the_window():
    ctx = _ctx(counters={"join_matches": [np.array([100, 40], np.int32),
                                          np.array([300, 60], np.int32)]})
    assert _reader("join.dtj_kept_match_share").read(ctx) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_none_without_their_source(name):
    """The parent program has no refined kernel, and returns no counts:
    each reader then reads None and does not raise."""
    empty = tr_mod.Trace(ops={0: [("fusion.1", 0, 5 * MS, "m")]},
                         host=[("bench.window", 0, 100 * MS)],
                         window=(0, 100 * MS))
    assert _reader(name).read(_ctx(tr=empty)) is None


def test_entry_watch_keeps_each_batchs_join_counts():
    """The entry's watch keeps the device counts ``run_dsc`` returns with
    each batch, and the reader turns them into the kept share; outside
    the watch nothing is kept and ``run_dsc`` is the program's own."""
    import jax
    from repro.core import dsc
    _, _, cfg, mix = run.load_cell("brest_day_tsa2_dt")
    cfg = {**cfg, "n_trajs": 32, "max_points": 16, "w": 3,
           "plan": {**cfg["plan"], "cluster_bu": 8, "cluster_bs": 128}}
    mix = {**mix, "fixes_min": 8, "fixes_max": 16, "pool": 2}
    entry = run.load_module(BENCH / "entries" / f"{cfg['entry']}.py")
    runner = entry.Runner(cfg, jax.devices())
    real = dsc.run_dsc
    counters = {}
    pool = generator.make_pool(5, cfg, mix)
    with runner.watch(counters):
        for b in pool:
            runner.run(b, runner.params(b))
    assert dsc.run_dsc is real
    assert len(counters["join_matches"]) == 2
    assert len(counters["join_tiles"]) == len(counters["sweep_tiles"]) == 2
    before, after = np.sum([np.asarray(c) for c in counters["join_matches"]],
                           axis=0)
    assert 0 < after < before
    share = _reader("join.dtj_kept_match_share").read(
        SimpleNamespace(counters=counters))
    assert share == pytest.approx(100.0 * after / before)
    runner.run(pool[0], runner.params(pool[0]))
    assert len(counters["join_matches"]) == 2
