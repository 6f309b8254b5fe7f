"""The cell ``brest_day_tsa2_dt`` on the CPU: the program under the
deployment's plan against the plain reference of TSA2 and DTJ's delta_t
refine (``bench/reference/dsc_batch_tsa2_dt.py``), planted faults that
have to read not correct, the bfloat16 control, and the reference's near
ties, each taken the other way.

    PYTHONPATH=src python -m pytest -q tests/bench/test_dtj_tsa2.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import generator  # noqa: E402
import run  # noqa: E402

CELL = "brest_day_tsa2_dt"
SEEDS = [2**31 + 1515, 3_000_001_517]


def _ref():
    return run.load_module(BENCH / "reference" / "dsc_batch_tsa2_dt.py")


def small() -> dict:
    """The cell's configuration at a size the CPU runs in seconds: 64
    tracks of at most 32 fixes, windows of 4, Pallas tiles the
    interpreter takes; ``delta_t`` and the segmentation are the cell's."""
    _, _, cfg, _ = run.load_cell(CELL)
    return {"n_trajs": 64, "max_points": 32, "w": 4,
            "plan": {**cfg["plan"], "cluster_bu": 8, "cluster_bs": 128}}


def small_mix() -> dict:
    _, _, _, mix = run.load_cell(CELL)
    return {**mix, "fixes_min": 8, "fixes_max": 32}


class Changed:
    """The program run with some of its parameters changed."""

    def __init__(self, inner, **change):
        self.inner, self.change = inner, change

    def params(self, b):
        return self.inner.params(b).replace(**self.change)

    def run(self, b, p):
        return self.inner.run(b, p)


FAULTS = {"sound": {}, "refine_skipped": {"delta_t": 0.0},
          "tsa1": {"segmentation": "tsa1"}, "delta_t_halved": {"delta_t": 60.0}}


def _run(seed, fault):
    change = FAULTS[fault]
    wrap = (lambda r: Changed(r, **change)) if change else None
    return run.run_cell(CELL, seed, 1.5, False, platform="cpu",
                        cfg_override=small(), mix_override=small_mix(),
                        runner_wrap=wrap, log=lambda *a, **k: None)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_equals_the_reference(seed):
    res = _run(seed, "sound")
    assert res["attempted"] >= 3
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["slots_mismatched"]["value"] == 0


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "sound"])
def test_planted_fault_reads_not_correct(fault):
    res = _run(SEEDS[0], fault)
    assert res["correct"] is False, res["checks"]


def test_control_fails():
    """The reference with its pair geometry in bfloat16 fails the cell's
    limits on every pool batch of a seed."""
    import jax.numpy as jnp
    _, _, cfg, _ = run.load_cell(CELL)
    cfg = {**cfg, **small()}
    ref = _ref()
    pool = generator.make_pool(SEEDS[0], cfg, small_mix())
    readings = []
    for b in pool:
        rp = run.reference_params(cfg, b)
        got = ref.reference_labels(b, rp, dtype=jnp.bfloat16)
        readings.append(compare.readings([got[f] for f in compare.FIELDS],
                                         ref.Answers(b, rp), cfg["limits"]))
    failed, _ = compare.judge(readings, cfg["limits"])
    assert failed == len(pool), readings


def test_reference_and_entry_see_the_deployment():
    """The harness passes the reference neither ``delta_t`` nor the
    segmentation: it reads both from the configuration file, and they are
    the values the entry runs the program with."""
    _, _, cfg, mix = run.load_cell(CELL)
    ref = _ref()
    dep = ref.deployment()
    assert (dep["delta_t"], dep["segmentation"]) == (cfg["delta_t"],
                                                     cfg["segmentation"])
    assert dep["delta_t"] == 2 * mix["sample_dt"] and dep["delta_t"] > 0
    assert "delta_t" not in run.reference_params(
        cfg, generator.make_pool(1, {**cfg, **small()}, small_mix())[0])
    import jax
    entry = run.load_module(BENCH / "entries" / f"{cfg['entry']}.py")
    b = generator.make_pool(1, {**cfg, **small()}, small_mix())[0]
    p = entry.Runner(cfg, jax.devices()).params(b)
    assert (p.delta_t, p.segmentation) == (dep["delta_t"],
                                           dep["segmentation"])


# ---------------------------------------------------------------------------
# Near ties, on hand-built batches: track 0 runs along y = 0 with a fix
# every 60 s, track 1 beside it; a fix of one matches the other's fix of
# the same index only (eps_t = 30 s).
# ---------------------------------------------------------------------------

EPS_SP, EPS_T, DT = 0.5, 30.0, 120.0


def _pair_batch(offsets):
    """Tracks 0 and 1 with a fix for each of ``offsets``; fix ``k`` of
    track 1 lies ``offsets[k]`` away from track 0's (None: far away); two
    more tracks far from both."""
    M = len(offsets)
    x = np.zeros((4, M), np.float32)
    y = np.zeros((4, M), np.float32)
    x[:2] = np.arange(M)
    y[1] = [50.0 if o is None else o for o in offsets]
    x[2:], y[2:] = 80.0, [[20.0], [40.0]]
    t = np.tile(np.arange(M, dtype=np.float32) * 60.0 + 1000.0, (4, 1))
    return generator.Batch(x, y, t, np.ones((4, M), bool),
                           np.arange(4, dtype=np.int32), EPS_SP, EPS_T)


def _params(**kw):
    return {"eps_sp": EPS_SP, "eps_t": EPS_T, "w": 2, "tau": 0.4,
            "alpha_sigma": -1.0, "k_sigma": -1.0, "max_subs": 2,
            "tie_margin": 1e-5, "delta_t": DT, "segmentation": "tsa2", **kw}


def _kept(words, r, c):
    """Fixes of track ``r`` that keep a match with track ``c``."""
    w = np.asarray(words)[r, :, c // 32]
    return [int(m) for m in np.nonzero((w >> np.uint32(c % 32)) & 1)[0]]


def test_run_of_exactly_delta_t_is_kept_and_no_tie():
    """Three matched fixes last 120 s = delta_t: kept (the condition is
    ``>=``), and no tie, since the duration is one float32 subtraction as
    in the program.  A run one float32 step shorter is dropped."""
    batch = _pair_batch([0.1, 0.1, 0.1, None, None, None])
    a = _ref().Answers(batch, _params())
    assert _kept(a.words, 0, 1) == [0, 1, 2]
    assert not [t for t in a.ties if t[0] == "match"]
    t_last = batch.t[:, 2].copy()
    batch.t[:, 2] = np.nextafter(t_last, np.float32(0))
    assert batch.t[0, 2] - batch.t[0, 0] < np.float32(DT)
    b = _ref().Answers(batch, _params())
    assert _kept(b.words, 0, 1) == []


def test_two_fixes_are_dropped_and_a_longer_run_is_kept():
    """A run of 60 s is dropped and one of 180 s kept; neither is a tie.
    At delta_t = 60 s both are kept."""
    offsets = [0.1, 0.1, None, 0.1, 0.1, 0.1, 0.1]
    a = _ref().Answers(_pair_batch(offsets), _params())
    assert _kept(a.words, 0, 1) == [3, 4, 5, 6]
    assert (a.vote[0, :2] == 0).all() and (a.vote[0, 3:] > 0).all()
    b = _ref().Answers(_pair_batch(offsets), _params(delta_t=60.0))
    assert _kept(b.words, 0, 1) == [0, 1, 3, 4, 5, 6]


def test_match_of_weight_near_zero_is_a_tie_and_its_other_outcome():
    """Fix 2 of track 1 lies exactly ``eps_sp`` away: weight 0, no match,
    so the run is fixes 0-1 (60 s) and is dropped.  Taken the other way,
    the match joins the run to 120 s and all three are kept."""
    a = _ref().Answers(_pair_batch([0.1, 0.1, EPS_SP, None, None, None]),
                       _params())
    assert _kept(a.words, 0, 1) == []
    assert ("match", 0, 2, 1) in a.ties and ("match", 1, 2, 0) in a.ties
    _, vote, _, same = a._flipped(0, 2, 1)
    assert not same
    assert (vote[0, :2] > 0).all() and (a.vote[0, :2] == 0).all()


def test_tsa2_signal_by_hand_and_its_ties():
    """One track of 8 fixes, w = 2: the neighbour sets {0}, {0}, {1},
    {1}, {0, 1}, {0, 1}, {2}, {2}.  The windows [n - 2, n - 1] and
    [n, n + 1] hold {0} and {1} at n = 2 (d = 1), {0, 1} and {0, 1} at
    n = 3 (d = 0), {1} and {0, 1} at n = 4 (d = 1/2), {0, 1} and
    {0, 1, 2} at n = 5 (d = 1/3); admissible n are 2..5 (n >= w and
    n <= count - w - 1)."""
    ref = _ref()
    words = np.array([1, 1, 2, 2, 3, 3, 4, 4], np.uint32)[None, :, None]
    valid = np.ones((1, 8), bool)
    d = ref.tsa2_signal(words, valid, 2)
    fin = np.isfinite(d[0])
    assert list(np.nonzero(fin)[0]) == [2, 3, 4, 5]
    assert d[0, 2:6] == pytest.approx([1.0, 0.0, 0.5, 1 / 3])
    # two peaks less than w apart and within rounding of each other are
    # near ties, and the other outcome moves the cut; two equal peaks are
    # equal ratios, decided alike (the left one), and no tie
    sig = np.full((1, 8), -np.inf)
    sig[0, 2:6] = [0.5, 0.9, 0.7, 0.9 - 3e-6]
    assert sorted(ref.signal_ties(sig, 3, 0.4, 1e-5)) == [(0, 3), (0, 5)]
    cut = lambda s: list(np.nonzero(np.diff(
        ref.base.tsa1_cuts(s, valid, 3, 0.4, 4)[0]))[0] + 1)
    assert cut(sig) == [3]
    sig[0, 5] += 2e-5
    assert cut(sig) == [5]
    sig[0, 5] = 0.9
    assert cut(sig) == [3]
    assert ref.signal_ties(sig, 3, 0.4, 1e-5) == []
    # a signal within rounding of tau is a tie, one equal to it is not
    at_tau = np.full((1, 8), -np.inf)
    at_tau[0, 3], at_tau[0, 5] = 0.4 + 4e-6, 0.4
    assert ref.signal_ties(at_tau, 2, 0.4, 1e-5) == [(0, 3)]
