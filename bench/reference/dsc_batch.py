"""Plain reference of single-host DSC (the paper's Algorithm 1 with P = 1).

Written from the definitions, and importing nothing of the program:

1. join (Problem 1): for every point ``(r, m)`` and every other track
   ``c``, the point of ``c`` inside the cylinder of radius ``eps_sp`` and
   half-height ``eps_t`` with the largest weight ``1 - d / eps_sp``
   (the first such point on a tie); ``delta_t`` is 0, so no run filter;
2. voting (Eq. 4): a point's vote is the sum of its best weights;
3. TSA1 (Algorithm 2): per track, the vote normalised by its maximum
   (Eq. 5); a cut where the difference of the means of the windows
   ``[n - w, n - 1]`` and ``[n, n + w - 1]`` exceeds ``tau`` and is the
   largest within ``w - 1`` on either side (strictly on the left); the
   first fix always starts a sub-trajectory, and sub-trajectories past
   the ``max_subs``-th merge into the last;
4. the ST relation: per slot (track, sub-trajectory) its fix count and
   mean vote (Eq. 6);
5. the SP relation (Eq. 2): each best weight adds to the cell (slot of the
   point, slot of its match), divided by the smaller fix count of the two
   slots, symmetrised by the larger of the two orientations;
6. thresholds (Sec. 6.1): ``alpha`` the mean of the positive similarities
   plus ``alpha_sigma`` standard deviations, ``k`` the mean slot vote plus
   ``k_sigma`` standard deviations;
7. Algorithm 4, one slot at a time in order of vote (ties by slot): an
   unclaimed slot with vote ``>= k`` becomes a representative and claims
   every non-representative slot whose similarity to it is positive,
   ``>= alpha`` and larger than the similarity it is held by.

A cut decision of step 3 whose signal lies within rounding of a tie (of
``tau``, or of the signal at a position less than ``w`` away) is settled
by the rounding of whoever computes it; ``Answers.alternatives`` gives the
labels with each such decision taken the other way.

The pair geometry runs on the device in blocks of ``rows`` tracks; the
window arithmetic of TSA1 and the threshold statistics run on the host in
float64.  ``dtype`` sets the precision of the pair geometry: float32 is
the reference, bfloat16 the control that a sound comparison has to fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _best(i, rows, x, y, t, valid, ids, eps_sp, eps_t, dtype, ref_valid=None):
    """Best weight and index ``[rows, M, T]`` of the tracks ``i*rows ..``
    (of their points in ``ref_valid``, all valid points by default)."""
    def sl(a):
        return lax.dynamic_slice_in_dim(a, i * rows, rows, 0)

    c = lambda a: a.astype(dtype)
    dx = c(sl(x))[:, :, None, None] - c(x)[None, None]
    dy = c(sl(y))[:, :, None, None] - c(y)[None, None]
    dt = jnp.abs(c(sl(t))[:, :, None, None] - c(t)[None, None])
    d = jnp.sqrt(dx * dx + dy * dy)
    ok = ((d <= c(eps_sp)) & (dt <= c(eps_t))
          & sl(valid if ref_valid is None else ref_valid)[:, :, None, None]
          & valid[None, None]
          & (sl(ids)[:, None] != ids[None, :])[:, None, :, None])
    w = jnp.where(ok, 1.0 - d / c(eps_sp), 0.0).astype(jnp.float32)
    best = jnp.max(w, axis=-1)
    idx = jnp.where(best > 0.0, jnp.argmax(w, axis=-1), -1)
    return best, idx


@functools.partial(jax.jit, static_argnames=("rows", "dtype"))
def point_votes(x, y, t, valid, ids, eps_sp, eps_t, *, rows, dtype):
    T, M = x.shape

    def blk(i):
        best, _ = _best(i, rows, x, y, t, valid, ids, eps_sp, eps_t, dtype)
        return jnp.sum(best, axis=-1)

    return lax.map(blk, jnp.arange(T // rows)).reshape(T, M)


@functools.partial(jax.jit, static_argnames=("rows", "dtype", "max_subs"))
def raw_similarity(x, y, t, valid, ids, sub, ref_valid, eps_sp, eps_t, *,
                   rows, dtype, max_subs):
    """``raw[slot(r, m), slot(c, best)] += best weight`` over the points
    ``(r, m)`` in ``ref_valid``: ``[S, S]``, its slots numbered
    sub-trajectory major (``q * T + r``, see ``cluster``) so that every
    block keeps the track axis minor."""
    T, M = x.shape
    ks = jnp.arange(max_subs)

    def blk(i):
        best, idx = _best(i, rows, x, y, t, valid, ids, eps_sp, eps_t, dtype,
                          ref_valid)
        csub = sub[jnp.arange(T)[None, None, :], jnp.maximum(idx, 0)]
        hit = (idx >= 0) & (csub >= 0)
        rsub = lax.dynamic_slice_in_dim(sub, i * rows, rows, 0)
        # [rows, q_ref, q_cand, M, T] summed over the points M
        sel = ((rsub[:, None, None, :, None] == ks[None, :, None, None, None])
               & (csub[:, None, None] == ks[None, None, :, None, None])
               & hit[:, None, None])
        return jnp.sum(jnp.where(sel, best[:, None, None], 0.0), axis=3)

    out = lax.map(blk, jnp.arange(T // rows))      # [T/rows, rows, q, q, T]
    S = T * max_subs
    return jnp.transpose(out.reshape(T, max_subs, max_subs, T),
                         (1, 0, 2, 3)).reshape(S, S)


def tsa1_signal(vote, valid, w: int) -> np.ndarray:
    """The window-difference signal ``d [T, M]`` in float64: ``-inf`` where
    no cut is admissible."""
    T, M = vote.shape
    v = np.where(valid, vote.astype(np.float64), 0.0)
    nv = np.where(valid, v / np.maximum(v.max(1, keepdims=True), 1e-12), 0.0)
    cnt = valid.astype(np.float64)
    n = np.arange(M)
    cs = np.concatenate([np.zeros((T, 1)), np.cumsum(nv, 1)], 1)
    cc = np.concatenate([np.zeros((T, 1)), np.cumsum(cnt, 1)], 1)

    def window_mean(lo, hi):                   # mean over [lo, hi)
        lo, hi = np.clip(lo, 0, M), np.clip(hi, 0, M)
        return (cs[:, hi] - cs[:, lo]) / np.maximum(cc[:, hi] - cc[:, lo], 1)

    d = np.abs(window_mean(n - w, n) - window_mean(n, n + w))
    count = valid.sum(1)
    ok = valid & (n[None] >= w) & (n[None] <= count[:, None] - w - 1)
    return np.where(ok, d, -np.inf)


def tsa1_cuts(d, valid, w: int, tau: float, max_subs: int) -> np.ndarray:
    """``sub [T, M]`` from the signal ``d``: each fix's sub-trajectory (-1
    on padding)."""
    T, M = d.shape
    pad = np.full((T, w), -np.inf)
    dp = np.concatenate([pad, d, pad], 1)
    left = np.max([dp[:, w + k: w + k + M] for k in range(-(w - 1), 0)]
                  or [np.full((T, M), -np.inf)], axis=0)
    right = np.max([dp[:, w + k: w + k + M] for k in range(1, w)]
                   or [np.full((T, M), -np.inf)], axis=0)
    cut = np.isfinite(d) & (d > left) & (d >= right) & (d > tau)
    cut |= valid & (np.cumsum(valid, 1) == 1)
    sub = np.clip(np.cumsum(cut, 1) - 1, 0, max_subs - 1)
    return np.where(valid, sub, -1).astype(np.int32)


def tsa1(vote, valid, w: int, tau: float, max_subs: int) -> np.ndarray:
    """``sub [T, M]``: each fix's sub-trajectory (-1 on padding)."""
    return tsa1_cuts(tsa1_signal(vote, valid, w), valid, w, tau, max_subs)


def near_ties(d, w: int, tau: float, margin: float) -> list[tuple[int, int]]:
    """The admissible positions ``(track, fix)`` whose cut decision lies
    within ``margin`` of a tie, closest first: ``d`` that close to ``tau``,
    or above ``tau - margin`` and that close to ``d`` at another position
    less than ``w`` away."""
    fin = np.isfinite(d)
    dd = np.where(fin, d, np.nan)
    pair = np.full(d.shape, np.nan)
    for k in range(1, w):
        diff = np.abs(dd[:, k:] - dd[:, :-k])
        pair[:, k:] = np.fmin(pair[:, k:], diff)
        pair[:, :-k] = np.fmin(pair[:, :-k], diff)
    pair = np.where(dd > tau - margin, pair, np.nan)
    close = np.fmin(np.abs(dd - tau), pair)
    r, m = np.nonzero(fin & (close < margin))
    order = np.argsort(close[r, m], kind="stable")
    return [(int(r[i]), int(m[i])) for i in order]


@jax.jit
def _finish_sim(raw, card, ok):
    """Eq. 2 over ``raw``, zero off the slots in ``ok`` and on the diagonal;
    with the per-row count, sum and sum of squares of its positive cells."""
    S = raw.shape[0]
    denom = jnp.maximum(jnp.minimum(card[:, None], card[None, :]), 1)
    sim = raw / denom.astype(jnp.float32)
    sim = jnp.maximum(sim, sim.T)
    idx = jnp.arange(S)
    sim = jnp.where(ok[:, None] & ok[None, :]
                    & (idx[:, None] != idx[None, :]), sim, 0.0)
    pos = sim > 0.0
    moments = (jnp.sum(pos, 1), jnp.sum(jnp.where(pos, sim, 0.0), 1),
               jnp.sum(jnp.where(pos, sim * sim, 0.0), 1))
    return sim, moments


@jax.jit
def _greedy(sim, order, slot_ok, slot_vote, alpha, k):
    """Algorithm 4 over ``sim [S, S]``, one visited slot per step."""
    S = sim.shape[0]
    slots = jnp.arange(S)

    def step(i, state):
        member_of, member_sim, is_rep = state
        s = order[i]
        new_rep = (slot_ok[s] & (member_of[s] < 0) & ~is_rep[s]
                   & (slot_vote[s] >= k))
        row = sim[s]
        claim = (new_rep & slot_ok & (row > 0.0) & (row >= alpha) & ~is_rep
                 & (slots != s) & (row > member_sim))
        member_of = jnp.where(claim, s, member_of)
        member_sim = jnp.where(claim, row, member_sim)
        member_of = member_of.at[s].set(jnp.where(new_rep, s, member_of[s]))
        member_sim = member_sim.at[s].set(
            jnp.where(new_rep, jnp.inf, member_sim[s]))
        is_rep = is_rep.at[s].set(is_rep[s] | new_rep)
        return member_of, member_sim, is_rep

    init = (jnp.full((S,), -1, jnp.int32), jnp.zeros((S,), jnp.float32),
            jnp.zeros((S,), bool))
    return lax.fori_loop(0, S, step, init)


def cluster(raw, card, ok, slot_vote, params: dict, nudge=None) -> dict:
    """Steps 5-7 over the slots in ``ok``: the labels, ``alpha`` and ``k``,
    and under ``ties`` the decisions of step 7 within ``tie_margin`` of a
    tie, each as the ``nudge`` that takes it the other way: ``("vote", s,
    delta)`` moves slot ``s``'s vote across ``k``, ``("sim", r, s,
    delta)`` representative ``r``'s similarity to ``s`` across ``alpha``
    or across the similarity ``s`` is held by.

    ``card``, ``ok``, ``slot_vote`` and the labels number a slot
    ``r * max_subs + q``; ``raw`` and the nudges number it ``q * T + r``."""
    S = card.shape[0]
    T = S // int(params["max_subs"])
    orig = (np.arange(S) % T) * (S // T) + np.arange(S) // T  # raw -> slot
    perm = np.argsort(orig)                                   # slot -> raw
    card, ok, slot_vote = card[orig], ok[orig], slot_vote[orig]
    sim, (cnt, s1, s2) = _finish_sim(raw, jnp.asarray(card, jnp.int32),
                                     jnp.asarray(ok))
    n_pos = max(int(np.asarray(cnt).sum()), 1)
    mean = np.asarray(s1, np.float64).sum() / n_pos
    var = max(np.asarray(s2, np.float64).sum() / n_pos - mean * mean, 0.0)
    alpha = mean + params["alpha_sigma"] * np.sqrt(var)
    nv = max(int(ok.sum()), 1)
    v_mean = slot_vote[ok].sum() / nv
    v_var = ((slot_vote[ok] - v_mean) ** 2).sum() / nv
    k = v_mean + params["k_sigma"] * np.sqrt(v_var)
    if nudge is not None:
        kind, *at = nudge
        if kind == "vote":
            slot_vote = slot_vote.copy()
            slot_vote[at[0]] += at[1]
        else:
            sim = sim.at[at[0], at[1]].add(at[2])

    key = np.where(ok, slot_vote, -np.inf)[perm]
    order = perm[np.argsort(-key, kind="stable")].astype(np.int32)
    member_of, member_sim, is_rep = _greedy(
        sim, jnp.asarray(order), jnp.asarray(ok),
        jnp.asarray(slot_vote, jnp.float32), jnp.float32(alpha),
        jnp.float32(k))
    margin = float(params.get("tie_margin", 0.0))
    ties = []
    if margin > 0 and nudge is None:
        ties = greedy_ties(sim, member_of, member_sim, is_rep, ok,
                           slot_vote, alpha, k, margin)
    del sim
    member_of = np.asarray(member_of)[perm]
    member_of = np.where(member_of >= 0, orig[np.maximum(member_of, 0)], -1)
    is_rep = np.asarray(is_rep)[perm]
    return {"member_of": member_of.astype(np.int32),
            "member_sim": np.where(is_rep, np.inf,
                                   np.asarray(member_sim)[perm]),
            "is_rep": is_rep,
            "is_outlier": ok[perm] & (member_of < 0),
            "alpha": float(alpha), "k": float(k), "ties": ties}


@jax.jit
def _rows(sim, idx):
    return sim[idx]


def greedy_ties(sim, member_of, member_sim, is_rep, ok, slot_vote, alpha, k,
                margin: float) -> list[tuple]:
    """The nudges of ``cluster``'s ``ties`` (raw numbering): slot votes
    within ``margin * max(1, |k|)`` of ``k``; a representative's positive
    similarity within ``margin`` of ``alpha``, or of the similarity at
    which another representative holds the slot."""
    k_margin = margin * max(1.0, abs(k))
    ties = [("vote", int(s), -2.0 * k_margin if slot_vote[s] >= k
             else 2.0 * k_margin)
            for s in np.nonzero(ok & (np.abs(slot_vote - k) < k_margin))[0]]
    member_of, member_sim, is_rep = (np.asarray(a) for a in
                                     (member_of, member_sim, is_rep))
    reps = np.nonzero(is_rep)[0]
    if len(reps) == 0:
        return ties
    # the representatives' rows, gathered at a padded count so that few
    # shapes compile
    idx = np.zeros(max(256, 1 << int(np.ceil(np.log2(len(reps))))),
                   np.int32)
    idx[:len(reps)] = reps
    rows = np.asarray(_rows(sim, jnp.asarray(idx)))[:len(reps)]  # [R, S]
    alpha = np.float32(alpha)
    pos = rows > 0.0
    held = (member_of >= 0) & ~is_rep
    near_alpha = pos & (np.abs(rows - alpha) < margin)
    near_held = (pos & held[None] & (np.abs(rows - member_sim[None]) < margin)
                 & (reps[:, None] != member_of[None]))
    for i, t in zip(*np.nonzero(near_alpha)):
        ties.append(("sim", int(reps[i]), int(t),
                     2.0 * margin if rows[i, t] < alpha else -2.0 * margin))
    for i, t in zip(*np.nonzero(near_held)):
        ties.append(("sim", int(reps[i]), int(t), 2.0 * margin))
    return ties


class Answers:
    """The reference's answers for one batch (host arrays ``x, y, t, valid,
    traj_id``) under ``params`` (``eps_sp, eps_t, w, tau, alpha_sigma,
    k_sigma, max_subs, tie_margin``).

    ``labels`` is its answer: numpy ``member_of, member_sim, is_rep,
    is_outlier`` (with ``alpha`` and ``k``).  A decision within
    ``tie_margin`` of a tie is settled by rounding, in the program as
    here, and may go either way: a TSA1 cut (``ties``, see ``near_ties``)
    or a decision of Algorithm 4 (``greedy_ties``, see ``cluster``).  For
    each, ``alternatives()`` gives the labels with that decision taken the
    other way, worked out the first time they are asked for."""

    def __init__(self, batch, params: dict, *, dtype=jnp.float32,
                 rows: int = 2):
        T = batch.x.shape[0]
        self.rows = rows = min(rows, T)
        if T % rows:
            raise ValueError(f"T={T} is not a multiple of rows={rows}")
        self.params, self.dtype = params, dtype
        self.arrs = [jnp.asarray(a) for a in (batch.x, batch.y, batch.t,
                                              batch.valid, batch.traj_id)]
        self.eps = (jnp.float32(params["eps_sp"]),
                    jnp.float32(params["eps_t"]))
        self.vote = np.asarray(point_votes(*self.arrs, *self.eps, rows=rows,
                                           dtype=dtype))
        self.valid = np.asarray(batch.valid)
        self.d = tsa1_signal(self.vote, self.valid, int(params["w"]))
        self.sub = self._cuts(self.d)
        self.labels = self.labels_for(self.sub)
        self.ties = near_ties(self.d, int(params["w"]), float(params["tau"]),
                              float(params.get("tie_margin", 0.0)))
        self.greedy_ties = self.labels.pop("ties")
        self._alts = None

    def _cuts(self, d):
        p = self.params
        return tsa1_cuts(d, self.valid, int(p["w"]), float(p["tau"]),
                         int(p["max_subs"]))

    def labels_for(self, sub, nudge=None) -> dict:
        """Steps 4-7 under the segmentation ``sub [T, M]`` (and the
        ``nudge`` of one decision of step 7, see ``cluster``)."""
        max_subs = int(self.params["max_subs"])
        T = sub.shape[0]
        slot = (np.arange(T)[:, None] * max_subs + sub)[self.valid]
        S = T * max_subs
        card = np.bincount(slot, minlength=S)
        slot_vote = np.bincount(
            slot, weights=self.vote[self.valid].astype(np.float64),
            minlength=S) / np.maximum(card, 1)
        raw = raw_similarity(*self.arrs, jnp.asarray(sub), self.arrs[3],
                             *self.eps, rows=self.rows, dtype=self.dtype,
                             max_subs=max_subs)
        return cluster(raw, card, card > 0, slot_vote, self.params, nudge)

    def alternatives(self) -> list[dict]:
        if self._alts is None:
            self._alts, seen = [], set()
            margin = float(self.params["tie_margin"])
            for r, m in self.ties:
                for nudge in (2.0 * margin, -2.0 * margin):
                    d = self.d.copy()
                    d[r, m] += nudge
                    sub = self._cuts(d)
                    key = (r, sub[r].tobytes())
                    if (sub[r] == self.sub[r]).all() or key in seen:
                        continue
                    seen.add(key)
                    self._alts.append(self.labels_for(sub))
            for nudge in self.greedy_ties:
                self._alts.append(self.labels_for(self.sub, nudge))
        return self._alts


def reference_labels(batch, params: dict, *, dtype=jnp.float32,
                     rows: int = 2) -> dict:
    """The reference's answer for ``batch``: ``Answers(...).labels``."""
    return Answers(batch, params, dtype=dtype, rows=rows).labels
