"""Plain reference of single-host DSC with TSA2 and DTJ's delta_t refine.

Written from the definitions, and importing nothing of the program.  From
the TSA1 reference beside it (``dsc_batch.py``, loaded by its path) it
takes what does not depend on the segmentation or on the run filter: the
cut rule (admissible positions, a strictly-left local maximum above
``tau``, the first fix, ``max_subs``), Eq. 2, the thresholds and
Algorithm 4.  The rest is written here:

1. join (Problem 1): for every point ``(r, m)`` and every other track
   ``c``, the point of ``c`` inside the cylinder of radius ``eps_sp`` and
   half-height ``eps_t`` with the largest weight ``1 - d / eps_sp`` (the
   first such point on a tie);
2. DTJ's refine (Problem 1's ``delta_t``): the matched fixes of track
   ``r`` against track ``c`` fall into runs of consecutive fixes; a run is
   kept iff ``t[last] - t[first] >= delta_t``, every other match is
   dropped.  A fix's run bounds are the last run start at or before it
   and the first run end at or after it, carried along the row with
   their times by a scan; the duration is the float32 difference of the
   batch's float32 times, one correctly rounded operation, as the
   program computes it, so a run's verdict is no near tie;
3. voting (Eq. 4): a point's vote is the sum of its kept weights;
4. TSA2 (Algorithm 3): a fix's neighbour set is the tracks it keeps a
   match with; ``d = 1 - |U1 & U2| / |U1 | U2|`` in float64 (0 where the
   union is empty), ``U1`` and ``U2`` the unions of the sets over
   ``[n - w, n - 1]`` and ``[n, n + w - 1]`` within the track's fixes;
   cuts by the TSA1 rule;
5. the ST and SP relations, thresholds and Algorithm 4 as ``dsc_batch``,
   the SP relation from the kept matches only.

``delta_t`` and ``segmentation`` are the deployment's, read from
``configs/brest_ais_tsa2_dt.json`` (the harness passes the reference
neither); a key of the same name in ``params`` takes their place.

Near ties: a decision within rounding of a tie goes whichever way the
arithmetic takes it, so the reference lists these decisions (``ties``)
and ``Answers.alternatives`` gives the labels with each taken the other
way:

* ``("cut", r, m)``: the TSA2 signal within ``tie_margin`` of ``tau`` or
  of a neighbour's signal, but not equal to it (``signal_ties``: equal
  signals are equal ratios of counts, decided alike);
* ``("match", r, m, c)``: a match whose weight lies within ``tie_margin``
  of 0 (the largest ``1 - d / eps_sp`` of ``c``'s points in time lies in
  ``(-tie_margin, tie_margin)``): its weight adds nothing, but a set bit
  is binary and a match joins runs.

The pair geometry and the run filter run on the device in blocks of
``rows`` tracks; the TSA2 signal and the thresholds run on the host in
float64.  ``dtype`` sets the precision of the pair geometry: float32 is
the reference, bfloat16 the control that a sound comparison has to fail.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HERE = Path(__file__).resolve().parent
CONFIG = HERE.parent / "configs" / "brest_ais_tsa2_dt.json"

_spec = importlib.util.spec_from_file_location(
    "bench_reference_dsc_batch_base", HERE / "dsc_batch.py")
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

NO_FLIP = (-1, -1, -1)


def deployment() -> dict:
    """The keys of the deployment's configuration file that the harness
    does not pass: ``delta_t``, ``segmentation``."""
    cfg = json.loads(CONFIG.read_text())
    return {k: cfg[k] for k in ("delta_t", "segmentation")}


def _pack(bits):
    """``[rows, M, T]`` bool -> ``[rows, M, ceil(T / 32)]`` uint32, track
    ``c`` at bit ``c % 32`` of word ``c // 32``."""
    rows, M, T = bits.shape
    W = -(-T // 32)
    b = jnp.pad(bits, ((0, 0), (0, 0), (0, W * 32 - T)))
    b = b.reshape(rows, M, W, 32).astype(jnp.uint32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _carry(flag, *vals, reverse=False):
    """Each of ``vals`` ``[rows, M, T]`` at the last position along the
    fixes (axis 1) at or before each (``reverse``: the first at or after)
    where ``flag`` holds; its own value where there is none."""
    def last(a, b):
        return (a[0] | b[0],) + tuple(jnp.where(b[0], vb, va)
                                      for va, vb in zip(a[1:], b[1:]))
    return lax.associative_scan(last, (flag, *vals), axis=1,
                                reverse=reverse)[1:]


def _block(i, rows, x, y, t, valid, ids, eps_sp, eps_t, delta_t, flip,
           margin, dtype):
    """The tracks ``i*rows ..`` against all: ``(best, idx, keep, near0)``
    ``[rows, M, T]``: the best weight and point of each candidate track,
    the matches the run filter keeps, and the matches within ``margin``
    of a weight of 0.  ``flip`` ``(r, m, c)`` toggles the match of fix
    ``(r, m)`` with track ``c`` (``NO_FLIP``: none)."""
    T, M = x.shape

    def sl(a):
        return lax.dynamic_slice_in_dim(a, i * rows, rows, 0)

    c = lambda a: a.astype(dtype)
    dx = c(sl(x))[:, :, None, None] - c(x)[None, None]
    dy = c(sl(y))[:, :, None, None] - c(y)[None, None]
    dt = jnp.abs(c(sl(t))[:, :, None, None] - c(t)[None, None])
    d = jnp.sqrt(dx * dx + dy * dy)
    in_time = ((dt <= c(eps_t)) & sl(valid)[:, :, None, None]
               & valid[None, None]
               & (sl(ids)[:, None] != ids[None, :])[:, None, :, None])
    # 1 - d / eps_sp of every point in time: positive exactly inside the
    # cylinder, so its largest, where positive, is the best weight
    u = jnp.where(in_time, (1.0 - d / c(eps_sp)).astype(jnp.float32),
                  -jnp.inf)
    u_max = jnp.max(u, axis=-1)
    best = jnp.maximum(u_max, 0.0)
    matched = best > 0.0
    near0 = jnp.abs(u_max) < margin
    idx = jnp.where(matched, jnp.argmax(u, axis=-1), -1)

    row = jnp.arange(rows)[:, None, None] + i * rows
    fix = jnp.arange(M)[None, :, None]
    cand = jnp.arange(T)[None, None, :]
    toggle = (row == flip[0]) & (fix == flip[1]) & (cand == flip[2])
    # the point a match toggled on is with: the flipped pair's nearest
    u_at = u[jnp.clip(flip[0] - i * rows, 0, rows - 1), jnp.clip(flip[1], 0),
             jnp.clip(flip[2], 0)]
    best = jnp.where(toggle, jnp.where(matched, 0.0, jnp.abs(u_max)), best)
    idx = jnp.where(toggle, jnp.where(matched, -1, jnp.argmax(u_at)), idx)
    matched = matched ^ toggle

    # run bounds: the last run start at or before each fix, the first run
    # end at or after it, with their times (carried along the row: a
    # gather of [rows, M, T] a block is slow on the TPU)
    before = jnp.pad(matched, ((0, 0), (1, 0), (0, 0)))[:, :M]
    after = jnp.pad(matched, ((0, 0), (0, 1), (0, 0)))[:, 1:]
    tr = jnp.broadcast_to(sl(t)[:, :, None], (rows, M, T))
    (t_first,) = _carry(matched & ~before, tr)
    (t_last,) = _carry(matched & ~after, tr, reverse=True)
    keep = matched & (t_last - t_first >= delta_t)
    return best, idx, keep, near0


@functools.partial(jax.jit, static_argnames=("rows", "dtype", "margin",
                                              "full"))
def point_pass(x, y, t, valid, ids, eps_sp, eps_t, delta_t, flip, i0=0, *,
               rows, dtype, margin, full=True):
    """Votes ``[T, M]`` (Eq. 4 over the kept matches) and the kept
    neighbour sets as words ``[T, M, W]``, with the ``near0`` mask of
    ``_block`` as words, over every block (``full``) or over the one
    block ``i0``."""
    T, M = x.shape

    def blk(i):
        best, _, keep, near0 = _block(i, rows, x, y, t, valid, ids, eps_sp,
                                      eps_t, delta_t, flip, margin, dtype)
        return (jnp.sum(jnp.where(keep, best, 0.0), axis=-1), _pack(keep),
                _pack(near0))

    if not full:
        return blk(i0)
    out = lax.map(blk, jnp.arange(T // rows))
    return tuple(a.reshape(T, *a.shape[2:]) for a in out)


@functools.partial(jax.jit, static_argnames=("rows", "dtype", "max_subs",
                                              "margin"))
def raw_similarity(x, y, t, valid, ids, sub, eps_sp, eps_t, delta_t, flip,
                   *, rows, dtype, max_subs, margin):
    """``raw[slot(r, m), slot(c, best)] += kept weight``: ``[S, S]``, its
    slots numbered sub-trajectory major (``q * T + r``) as
    ``dsc_batch.raw_similarity``."""
    T, M = x.shape
    ks = jnp.arange(max_subs)

    def blk(i):
        best, idx, keep, _ = _block(i, rows, x, y, t, valid, ids, eps_sp,
                                    eps_t, delta_t, flip, margin, dtype)
        # the sub-slot of each match's point, selected, not gathered (as
        # the run bounds: a gather of this size a block is slow on the TPU)
        csub = jnp.max(jnp.where(idx[..., None] == jnp.arange(M),
                                 sub[None, None], -1), axis=-1)
        hit = keep & (idx >= 0) & (csub >= 0)
        rsub = lax.dynamic_slice_in_dim(sub, i * rows, rows, 0)
        sel = ((rsub[:, None, None, :, None] == ks[None, :, None, None, None])
               & (csub[:, None, None] == ks[None, None, :, None, None])
               & hit[:, None, None])
        return jnp.sum(jnp.where(sel, best[:, None, None], 0.0), axis=3)

    out = lax.map(blk, jnp.arange(T // rows))
    S = T * max_subs
    return jnp.transpose(out.reshape(T, max_subs, max_subs, T),
                         (1, 0, 2, 3)).reshape(S, S)


def tsa2_signal(words, valid, w: int) -> np.ndarray:
    """The windowed-Jaccard signal ``d [T, M]`` in float64 from neighbour
    words ``[T, M, W]``: ``-inf`` where no cut is admissible."""
    words = np.where(valid[..., None], np.asarray(words), np.uint32(0))
    T, M, _ = words.shape
    u1 = np.zeros_like(words)
    u2 = np.zeros_like(words)
    for k in range(1, w + 1):           # [n - w, n - 1]
        u1[:, k:] |= words[:, :M - k]
    for k in range(w):                  # [n, n + w - 1]
        u2[:, :M - k] |= words[:, k:]
    inter = np.bitwise_count(u1 & u2).sum(-1, dtype=np.int64)
    union = np.bitwise_count(u1 | u2).sum(-1, dtype=np.int64)
    d = np.where(union > 0, 1.0 - inter / np.maximum(union, 1), 0.0)
    n = np.arange(M)
    count = valid.sum(1)
    ok = valid & (n[None] >= w) & (n[None] <= count[:, None] - w - 1)
    return np.where(ok, d, -np.inf)


def signal_ties(d, w: int, tau: float, margin: float) -> list[tuple[int, int]]:
    """The admissible positions ``(track, fix)`` whose TSA2 cut decision
    lies within ``margin`` of a tie: ``d`` that close to ``tau``, or above
    ``tau - margin`` and that close to ``d`` at another position less than
    ``w`` away, but not equal to either.  ``d`` is one minus a ratio of
    counts, so equal signals are equal ratios: a correctly rounded
    division gives them equal float32 values too, and the strict-left
    rule (or the strict ``> tau``) decides them alike in the program and
    here."""
    fin = np.isfinite(d)
    dd = np.where(fin, d, np.nan)
    pair = np.full(d.shape, np.nan)
    for k in range(1, w):
        diff = np.abs(dd[:, k:] - dd[:, :-k])
        diff = np.where(diff > 0, diff, np.nan)
        pair[:, k:] = np.fmin(pair[:, k:], diff)
        pair[:, :-k] = np.fmin(pair[:, :-k], diff)
    pair = np.where(dd > tau - margin, pair, np.nan)
    to_tau = np.abs(dd - tau)
    close = np.fmin(np.where(to_tau > 0, to_tau, np.nan), pair)
    r, m = np.nonzero(fin & (close < margin))
    order = np.argsort(close[r, m], kind="stable")
    return [(int(r[i]), int(m[i])) for i in order]


def _bits(words) -> list[tuple[int, int, int]]:
    """``(r, m, c)`` of every set bit of ``[T, M, W]`` words."""
    words = np.asarray(words)
    out = []
    for r, m, q in zip(*np.nonzero(words)):
        v = int(words[r, m, q])
        out += [(int(r), int(m), 32 * int(q) + b) for b in range(32)
                if v >> b & 1]
    return out


class Answers:
    """The reference's answers for one batch (host arrays ``x, y, t, valid,
    traj_id``) under ``params`` (``eps_sp, eps_t, w, tau, alpha_sigma,
    k_sigma, max_subs, tie_margin``; ``delta_t`` and ``segmentation`` from
    the deployment's file unless given).

    ``labels`` is its answer: numpy ``member_of, member_sim, is_rep,
    is_outlier`` (with ``alpha`` and ``k``).  ``ties`` lists the decisions
    within rounding of a tie (see the module's docstring) and
    ``greedy_ties`` those of Algorithm 4 (``dsc_batch.cluster``);
    ``alternatives()`` gives the labels with each taken the other way,
    worked out the first time they are asked for."""

    def __init__(self, batch, params: dict, *, dtype=jnp.float32,
                 rows: int = 2):
        T = batch.x.shape[0]
        self.rows = rows = min(rows, T)
        if T % rows:
            raise ValueError(f"T={T} is not a multiple of rows={rows}")
        p = self.params = {**deployment(), **params}
        if p["segmentation"] != "tsa2":
            raise ValueError(f"segmentation {p['segmentation']!r}: this "
                             "reference is TSA2's")
        self.dtype = dtype
        self.arrs = [jnp.asarray(a) for a in (batch.x, batch.y, batch.t,
                                              batch.valid, batch.traj_id)]
        self.scalars = (jnp.float32(p["eps_sp"]), jnp.float32(p["eps_t"]),
                        jnp.float32(p["delta_t"]))
        self.kw = dict(rows=rows, dtype=dtype,
                       margin=float(p.get("tie_margin", 0.0)))
        self.valid = np.asarray(batch.valid)
        vote, words, near0 = point_pass(
            *self.arrs, *self.scalars, jnp.asarray(NO_FLIP, jnp.int32),
            **self.kw)
        self.vote, self.words = np.asarray(vote), np.asarray(words)
        self.d = tsa2_signal(self.words, self.valid, int(p["w"]))
        self.sub = self._cuts(self.d)
        self.labels = self.labels_for(self.sub, self.vote)
        self.ties = ([("cut", r, m) for r, m in signal_ties(
            self.d, int(p["w"]), float(p["tau"]),
            float(p.get("tie_margin", 0.0)))]
                     + [("match",) + k for k in _bits(near0)])
        self.greedy_ties = self.labels.pop("ties")
        self._alts = None

    def _cuts(self, d, r=None):
        """``sub`` of the signal ``d``: of every track, or of the one
        track ``r`` (``d`` then its row)."""
        p = self.params
        valid = self.valid if r is None else self.valid[r:r + 1]
        return base.tsa1_cuts(d, valid, int(p["w"]), float(p["tau"]),
                              int(p["max_subs"]))

    def labels_for(self, sub, vote, flip=NO_FLIP, nudge=None) -> dict:
        """Steps 5 on under the segmentation ``sub [T, M]`` and the votes
        ``vote [T, M]``, with ``flip`` (see ``_block``) and the ``nudge``
        of one decision of Algorithm 4 (see ``dsc_batch.cluster``)."""
        max_subs = int(self.params["max_subs"])
        T = sub.shape[0]
        slot = (np.arange(T)[:, None] * max_subs + sub)[self.valid]
        S = T * max_subs
        card = np.bincount(slot, minlength=S)
        slot_vote = np.bincount(
            slot, weights=vote[self.valid].astype(np.float64),
            minlength=S) / np.maximum(card, 1)
        raw = raw_similarity(*self.arrs, jnp.asarray(sub), *self.scalars,
                             jnp.asarray(flip, jnp.int32),
                             max_subs=max_subs, **self.kw)
        return base.cluster(raw, card, card > 0, slot_vote, self.params,
                            nudge)

    def _flipped(self, r, m, c):
        """Votes, segmentation and whether anything but the weight of the
        match of fix ``(r, m)`` with track ``c`` moved, with that match,
        within rounding of a weight of 0, taken the other way."""
        flip = (r, m, c)
        i = r // self.rows
        lo, hi = i * self.rows, (i + 1) * self.rows
        vote_b, words_b, _ = point_pass(
            *self.arrs, *self.scalars, jnp.asarray(flip, jnp.int32), i,
            full=False, **self.kw)
        vote, words = self.vote.copy(), self.words.copy()
        vote[lo:hi], words[lo:hi] = np.asarray(vote_b), np.asarray(words_b)
        sub = self.sub.copy()
        sub[r] = self._cuts(tsa2_signal(words[r:r + 1], self.valid[r:r + 1],
                                        int(self.params["w"])), r)[0]
        moved = words[r] ^ self.words[r]
        moved[m, c // 32] &= ~np.uint32(1 << (c % 32))
        same = (sub[r] == self.sub[r]).all() and not moved.any()
        return flip, vote, sub, same

    def alternatives(self) -> list[dict]:
        if self._alts is None:
            self._alts, seen = [], set()
            margin = float(self.params["tie_margin"])
            for kind, r, m, *c in self.ties:
                if kind == "cut":
                    for nudge in (2.0 * margin, -2.0 * margin):
                        d = self.d[r:r + 1].copy()
                        d[0, m] += nudge
                        row = self._cuts(d, r)[0]
                        key = (r, row.tobytes())
                        if (row == self.sub[r]).all() or key in seen:
                            continue
                        seen.add(key)
                        sub = self.sub.copy()
                        sub[r] = row
                        self._alts.append(self.labels_for(sub, self.vote))
                    continue
                flip, vote, sub, same = self._flipped(r, m, c[0])
                if not same:
                    self._alts.append(self.labels_for(sub, vote, flip))
            for nudge in self.greedy_ties:
                self._alts.append(self.labels_for(self.sub, self.vote,
                                                  nudge=nudge))
        return self._alts


def reference_labels(batch, params: dict, *, dtype=jnp.float32,
                     rows: int = 2) -> dict:
    """The reference's answer for ``batch``: ``Answers(...).labels``."""
    return Answers(batch, params, dtype=dtype, rows=rows).labels
