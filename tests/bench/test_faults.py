"""The comparison that decides ``correct`` fails what it has to fail.

Each test drives a whole run of a cell on the CPU at a small size (the
harness's look for a chip skipped), with a fault planted under the timed
path, and sees ``correct`` come out false; the sound run beside them sees
it true.  The control, the plain reference computed in bfloat16, fails
the comparison's limits too.

    PYTHONPATH=src python -m pytest -q tests/bench
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

SEED = 2**31 + 4242
CELL = "brest_day"


def small(cell: str) -> dict:
    """The cell's configuration at a size the CPU runs in seconds: 64
    tracks of at most 32 fixes, windows of 4, Pallas tiles the
    interpreter takes."""
    _, _, cfg, _ = run.load_cell(cell)
    return {"n_trajs": 64, "max_points": 32, "w": 4,
            "plan": {**cfg["plan"], "cluster_bu": 8, "cluster_bs": 128}}


def small_mix(cell: str) -> dict:
    """The cell's traffic with tracks that fit ``small``'s rows."""
    _, _, _, mix = run.load_cell(cell)
    return {**mix, "fixes_min": 8, "fixes_max": 32}


class Stale:
    """A step that returns its state unchanged: each call answers with
    the labels of the call before it."""

    def __init__(self, inner):
        self.inner, self.last = inner, None

    def params(self, b):
        return self.inner.params(b)

    def run(self, b, p):
        got = self.inner.run(b, p)
        out, self.last = (self.last if self.last is not None else got), got
        return out


class HalfBatch(Stale):
    """Half of the batch left out: the second half of the tracks is
    padding to the program."""

    def run(self, b, p):
        valid = b.valid.copy()
        valid[b.valid.shape[0] // 2:] = False
        return self.inner.run(generator.Batch(b.x, b.y, b.t, valid,
                                              b.traj_id, b.eps_sp, b.eps_t),
                              p)


class Altered(Stale):
    """An answer altered where it is produced: the first member moves to
    another representative's cluster, or becomes an outlier."""

    def run(self, b, p):
        member_of, member_sim, is_rep, is_outlier = (
            np.asarray(a) for a in self.inner.run(b, p))
        member_of = member_of.copy()
        s = int(np.nonzero((member_of >= 0) & ~is_rep)[0][0])
        reps = np.nonzero(is_rep)[0]
        member_of[s] = next((int(r) for r in reps if r != member_of[s]), -1)
        return member_of, member_sim, is_rep, is_outlier


FAULTS = {"sound": None, "stale": Stale, "half_batch": HalfBatch,
          "altered": Altered}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_batch_cell_fault_fails(fault):
    res = run.run_cell(CELL, SEED, 0.5, False, platform="cpu",
                       cfg_override=small(CELL), mix_override=small_mix(CELL),
                       runner_wrap=FAULTS[fault], log=lambda *a, **k: None)
    assert res["attempted"] >= 1
    assert res["correct"] is (fault == "sound"), res["checks"]
    assert list(res)[-1] == "checks"


def test_control_fails():
    """The reference with its pair geometry in bfloat16, in the program's
    place, fails the cell's limits on every pool batch of a seed."""
    import jax.numpy as jnp
    _, _, cfg, _ = run.load_cell(CELL)
    cfg = {**cfg, **small(CELL)}
    ref = run.load_module(BENCH / "reference" / f"{cfg['reference']}.py")
    pool = generator.make_pool(SEED, cfg, small_mix(CELL))
    readings = []
    for b in pool:
        rp = run.reference_params(cfg, b)
        want = ref.Answers(b, rp)
        got = ref.reference_labels(b, rp, dtype=jnp.bfloat16)
        readings.append(compare.readings([got[f] for f in compare.FIELDS],
                                         want, cfg["limits"]))
    failed, _ = compare.judge(readings, cfg["limits"])
    assert failed == len(pool), readings


def test_near_ties_and_their_other_cut():
    """A signal with two equal peaks less than ``w`` apart: the cut falls
    on the first, both are near ties, and nudging the second up moves the
    cut there."""
    ref = run.load_module(BENCH / "reference" / "dsc_batch.py")
    w, tau = 4, 0.4
    valid = np.ones((1, 16), bool)
    d = np.full((1, 16), -np.inf)
    d[0, 4:12] = [0.1, 0.5, 0.2, 0.5 - 1e-9, 0.1, 0.0, 0.3, 0.1]
    sub = ref.tsa1_cuts(d, valid, w, tau, 8)
    assert list(np.nonzero(np.diff(sub[0]))[0] + 1) == [5]
    ties = ref.near_ties(d, w, tau, 1e-6)
    assert sorted(ties) == [(0, 5), (0, 7)]
    d2 = d.copy()
    d2[0, 7] += 2e-6
    sub2 = ref.tsa1_cuts(d2, valid, w, tau, 8)
    assert list(np.nonzero(np.diff(sub2[0]))[0] + 1) == [7]
    assert ref.near_ties(d, w, tau, 1e-10) == []


class _Want:
    """Reference answers by hand: ``labels`` and its alternatives, with a
    count of the times the alternatives were asked for."""

    def __init__(self, labels, alts):
        self.labels, self._alts, self.asked = labels, alts, 0

    def alternatives(self):
        self.asked += 1
        return self._alts


def _labels(member_of, member_sim):
    member_of = np.asarray(member_of, np.int32)
    is_rep = member_of == np.arange(len(member_of))
    sim = np.where(is_rep, np.inf, np.asarray(member_sim, np.float32))
    return {"member_of": member_of, "member_sim": sim, "is_rep": is_rep,
            "is_outlier": member_of < 0}


def test_readings_hold_each_slot_to_the_answer_it_agrees_with():
    """A slot that a tie's alternative explains is not mismatched, its gap
    is taken against that alternative; a slot that no reference answer
    gives stays mismatched; the alternatives are not asked for while the
    reference's labels explain the answer."""
    limits = {"slots_mismatched": 0, "member_sim_gap": 1e-3}
    primary = _labels([0, 0, 0, 3, 3, -1], [0, .5, .6, 0, .7, 0])
    alt = _labels([0, 0, 3, 3, 3, -1], [0, .5, .8, 0, .7, 0])
    as_got = lambda lab: [lab[f] for f in compare.FIELDS]

    want = _Want(primary, [alt])
    r = compare.readings(as_got(primary), want, limits)
    assert (r["slots_mismatched"], want.asked) == (0, 0)

    r = compare.readings(as_got(alt), want, limits)
    assert r == {"slots_mismatched": 0, "member_sim_gap": 0.0,
                 "alternatives": 1}

    moved = _labels([0, 0, 3, 3, 0, -1], [0, .5, .8, 0, .7, 0])
    r = compare.readings(as_got(moved), want, limits)
    assert r["slots_mismatched"] == 1
    assert compare.judge([r], limits)[0] == 1

    off = _labels([0, 0, 3, 3, 3, -1], [0, .5, .85, 0, .7, 0])
    r = compare.readings(as_got(off), want, limits)
    assert r["slots_mismatched"] == 0
    assert r["member_sim_gap"] == pytest.approx(0.05, abs=1e-6)


@pytest.mark.parametrize("vote1, ties, other", [
    (4.0, [("sim", 0, 2, 2e-5)], [0, 1, 0, -1]),
    (2.0000001, [("vote", 1, -4e-5), ("sim", 0, 2, 2e-5)], [0, -1, 0, -1]),
])
def test_greedy_ties_and_their_other_outcome(vote1, ties, other):
    """Four slots, slot 2 held by representative 1 at a similarity within
    rounding of representative 0's (and slot 1's vote within rounding of
    ``k``): the ties are found, and the first one's nudge takes its
    decision the other way."""
    import jax.numpy as jnp
    ref = run.load_module(BENCH / "reference" / "dsc_batch.py")
    raw = (jnp.zeros((4, 4), jnp.float32).at[0, 2].set(0.5)
           .at[1, 2].set(0.5 + 1.2e-7).at[2, 3].set(0.1))
    card = np.ones(4, np.int64)
    vote = np.array([5.0, vote1, 1.0, 1.0 if vote1 == 4.0 else 0.0])
    params = {"max_subs": 1, "alpha_sigma": -10.0, "k_sigma": 0.0,
              "tie_margin": 1e-5}
    lab = ref.cluster(raw, card, card > 0, vote, params)
    assert list(lab["member_of"]) == [0, 1, 1, -1]
    assert [t[:-1] for t in lab["ties"]] == [t[:-1] for t in ties]
    assert [t[-1] for t in lab["ties"]] == pytest.approx([t[-1] for t in ties])
    alt = ref.cluster(raw, card, card > 0, vote, params, lab["ties"][0])
    assert list(alt["member_of"]) == other
