"""Subtrajectory join (Problem 1 / DTJ) — pure-jnp reference path.

The dense formulation: for every reference point ``(r, m)`` and every candidate
trajectory ``c``, find the candidate point inside the spatiotemporal cylinder
(radius ``eps_sp``, half-height ``eps_t``) with the highest proximity weight
``1 - d_s / eps_sp``.  This is exactly the quantity DTJ's Refine step feeds to
the voting (Eq. 4) and to the weighted-LCSS similarity (Eq. 2): the single
matching point ``s_k`` of trajectory ``s`` for point ``r_i``.

The Pallas kernel in ``repro.kernels.stjoin`` computes the same contraction
with explicit VMEM tiling; ``tests/test_kernels_stjoin.py`` asserts allclose
against this reference.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.types import JoinResult, TrajectoryBatch


def best_match_join(
    ref: TrajectoryBatch,
    cand: TrajectoryBatch,
    eps_sp: float | jnp.ndarray,
    eps_t: float | jnp.ndarray,
    *,
    exclude_same_id: bool = True,
    prune_mask: jnp.ndarray | None = None,
) -> JoinResult:
    """Dense best-match spatiotemporal join (reference implementation).

    Returns weight/index tensors of shape ``[T_ref, M_ref, T_cand]``.
    Memory is O(T*M*C) — fine for tests; the distributed pipeline streams
    candidate tiles through the Pallas kernel instead.

    ``prune_mask``: optional [T_ref, T_cand] bool from the spatiotemporal
    index (``repro.index.grid.trajectory_pair_mask``); pairs masked False
    are skipped.  A conservative mask leaves the result unchanged.
    """
    # [T, M, 1, 1] vs [1, 1, C, Mc] broadcasting
    dx = ref.x[:, :, None, None] - cand.x[None, None, :, :]
    dy = ref.y[:, :, None, None] - cand.y[None, None, :, :]
    dt = jnp.abs(ref.t[:, :, None, None] - cand.t[None, None, :, :])
    d_sp = jnp.sqrt(dx * dx + dy * dy)

    ok = (d_sp <= eps_sp) & (dt <= eps_t)
    ok &= ref.valid[:, :, None, None] & cand.valid[None, None, :, :]
    if exclude_same_id:
        same = ref.traj_id[:, None] == cand.traj_id[None, :]      # [T, C]
        ok &= ~same[:, None, :, None]
    if prune_mask is not None:
        ok &= prune_mask[:, None, :, None]

    w = jnp.where(ok, 1.0 - d_sp / eps_sp, 0.0)                   # [T, M, C, Mc]
    best_w = jnp.max(w, axis=-1)                                  # [T, M, C]
    best_idx = jnp.where(
        best_w > 0.0, jnp.argmax(w, axis=-1).astype(jnp.int32), -1)
    return JoinResult(best_w=best_w, best_idx=best_idx)


def filter_delta_t(join: JoinResult, ref_t: jnp.ndarray,
                   delta_t: float | jnp.ndarray) -> JoinResult:
    """DTJ Refine: drop matches whose common subsequence lasts < ``delta_t``.

    For each (ref trajectory r, candidate c) pair, the matched reference
    points form runs of consecutive samples; a run whose time extent
    ``t[last] - t[first]`` is below ``delta_t`` is discarded (the paper's
    condition (a) of Problem 1: both matched subtrajectories must span at
    least ``delta_t``).  ``ref_t``: [T, M] reference point times.
    """
    T, M, C = join.best_w.shape
    matched = join.best_w > 0.0                                   # [T, M, C]
    matched_mc = jnp.moveaxis(matched, 1, 2)                      # [T, C, M]

    # run ids: new run whenever the match indicator turns on after a gap.
    starts = matched_mc & ~jnp.pad(matched_mc, ((0, 0), (0, 0), (1, 0)))[..., :M]
    run_id = jnp.cumsum(starts, axis=-1) - 1                      # [T, C, M]
    run_id = jnp.where(matched_mc, run_id, M - 1)                 # park unmatched

    t_b = jnp.broadcast_to(ref_t[:, None, :], (T, C, M))
    big = jnp.float32(jnp.finfo(jnp.float32).max)

    flat_runs = run_id.reshape(T * C, M)
    flat_t = t_b.reshape(T * C, M)
    seg = flat_runs + (jnp.arange(T * C)[:, None] * M)            # global seg ids

    def seg_reduce(vals, fill, op):
        out = jnp.full((T * C * M,), fill, vals.dtype)
        return op(out, seg.reshape(-1), vals.reshape(-1))

    t_min = seg_reduce(jnp.where(matched_mc.reshape(T * C, M), flat_t, big),
                       big, lambda o, s, v: o.at[s].min(v))
    t_max = seg_reduce(jnp.where(matched_mc.reshape(T * C, M), flat_t, -big),
                       -big, lambda o, s, v: o.at[s].max(v))
    dur = (t_max - t_min).reshape(T, C, M)                        # per run id
    keep_run = dur >= delta_t
    keep = jnp.take_along_axis(keep_run, run_id, axis=-1) & matched_mc
    keep = jnp.moveaxis(keep, 2, 1)                               # [T, M, C]

    return JoinResult(
        best_w=jnp.where(keep, join.best_w, 0.0),
        best_idx=jnp.where(keep, join.best_idx, -1),
    )


def refine_join(join: JoinResult, ref_t: jnp.ndarray,
                delta_t: float) -> JoinResult:
    """``filter_delta_t`` when ``delta_t > 0``, else the join unchanged.

    ``delta_t`` is a concrete number (``DSCParams.delta_t`` is a static
    field), so the choice is made at trace time: a ``lax.cond`` would
    compile (and hold memory for) the filter branch even at
    ``delta_t == 0``, and on a ``[T, M, C]`` cube that branch alone can
    exceed a chip's HBM.
    """
    if float(delta_t) > 0:
        return filter_delta_t(join, ref_t, jnp.float32(delta_t))
    return join


def subtrajectory_join(ref: TrajectoryBatch, cand: TrajectoryBatch,
                       eps_sp, eps_t, delta_t=0.0, *,
                       use_index: bool = False) -> JoinResult:
    """Problem 1, end to end: cylinder join + delta_t run filtering.

    ``use_index=True`` applies the row-level spatiotemporal prune mask
    (bbox distance test per trajectory pair) before the dense sweep; the
    mask is conservative, so the output is unchanged.
    """
    prune_mask = None
    if use_index:
        from repro.index.grid import trajectory_pair_mask
        prune_mask = trajectory_pair_mask(
            ref.x, ref.y, ref.t, ref.valid,
            cand.x, cand.y, cand.t, cand.valid, eps_sp, eps_t)
    j = best_match_join(ref, cand, eps_sp, eps_t, prune_mask=prune_mask)
    return refine_join(j, ref.t, delta_t)
