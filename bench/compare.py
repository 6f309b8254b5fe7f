"""The comparison that decides ``correct`` for cells whose answer is a
clustering of sub-trajectory slots.

Each answer the window produced is held against the plain reference's
answers of the same batch (``want``: its ``labels``, and the
``alternatives()`` that differ from them only in a decision settled by
rounding).  Two numbers are compared, each with its limit from the
configuration file (``limits``):

* ``slots_mismatched``: slots whose representative or state
  (representative, member, outlier) differs from the reference's;
* ``member_sim_gap``: the largest difference of a member's similarity to
  its representative, over the slots that both give the same
  representative.

Where the answer is within the limits of the reference's labels, the
alternatives are not looked at.  Else each slot is held against the
reference answer that it agrees with best: a slot is mismatched where it
differs from every one, and its gap is the smallest of those it agrees
with.  A run's number is the largest over its answers.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("member_of", "member_sim", "is_rep", "is_outlier")


def _slots(got, want: dict):
    """``(mismatched [S], member-similarity gap [S])`` of ``got`` against
    one reference answer."""
    member_of, member_sim, is_rep, is_outlier = got
    mism = ((member_of != want["member_of"]) | (is_rep != want["is_rep"])
            | (is_outlier != want["is_outlier"]))
    same = (~mism) & (member_of >= 0) & ~is_rep
    gap = np.abs(np.where(same, member_sim, 0).astype(np.float64)
                 - np.where(same, want["member_sim"], 0).astype(np.float64))
    return mism, gap


def _numbers(mism, gap, alternatives: int) -> dict:
    return {"slots_mismatched": int(mism.sum()),
            "member_sim_gap": float(gap[~mism].max(initial=0.0)),
            "alternatives": alternatives}


def within(r: dict, limits: dict) -> bool:
    return all(r[k] <= limits[k] for k in limits)


def readings(got, want, limits: dict) -> dict:
    """Both numbers for one answer ``got`` (the four label arrays, in
    ``FIELDS`` order) against the reference's answers ``want``, with the
    count of alternatives looked at."""
    got = [np.asarray(a) for a in got]
    mism, gap = _slots(got, want.labels)
    r = _numbers(mism, gap, 0)
    if within(r, limits):
        return r
    alts = want.alternatives()
    for alt in alts:
        m2, g2 = _slots(got, alt)
        better = ~m2 & (mism | (g2 < gap))
        gap = np.where(better, g2, gap)
        mism &= m2
    return _numbers(mism, gap, len(alts))


def judge(per_answer: list[dict], limits: dict) -> tuple[int, dict]:
    """``(failed answers, {name: {"value": worst, "limit": limit}})``."""
    failed = sum(not within(r, limits) for r in per_answer)
    checks = {k: {"value": max((r[k] for r in per_answer), default=None),
                  "limit": limits[k]} for k in limits}
    return failed, checks
