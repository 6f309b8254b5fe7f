"""Pallas tile kernels for the round-parallel greedy clustering engine.

Contract (see ``repro.core.clustering.cluster_rounds`` and ``ref.py``)
---------------------------------------------------------------------
The round engine iterates two ``[S, S]`` reductions over the dense
similarity matrix:

* ``_round_scan_kernel``  per round: for every column (slot) ``s``, OR-
  reduce over rows ``u`` the alpha-edge predicate
  ``sim[u, s] > 0 and sim[u, s] >= alpha and rank[u] < rank[s]`` masked by
  the round state — ``unresolved[u]`` yields ``blocked[s]`` (s must wait),
  ``is_rep[u]`` yields ``claimed[s]`` (s resolves as non-rep now).  One
  sweep fuses the eligibility scan, the threshold test and both masks.
* ``_assign_kernel``      once, after the representative set converges:
  per column, the running (max weight, min visit rank, slot) accumulator
  over representative rows — the claim-max that replaces Algorithm 4's
  sequential reassignment updates.

Tiling
------
grid = (S/bs, S/bu); column block ``j`` (axis 0) owns the output block and
is revisited across the row-block axis ``i`` (axis 1, fastest) with the
accumulator resident in VMEM — the same "contraction last axis" layout as
``kernels/stjoin/stjoin.py``.  Per-tile working set at the (8, 128)
default is a single f32 VPU register tile plus [bs] accumulators, so VMEM
holds the entire round state; the only HBM traffic per round is one read
of the ``[S, S]`` matrix and O(S) state vectors — compare the sequential
oracle, which makes S dependent row reads that no pipeline can overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

_BIG_RANK = 2**31 - 1          # python int: kernels may not capture arrays

# Layout: row-side state is a [S, 1] column (block (bu, 1)), column-side
# state and every per-slot output a [1, S] row (block (1, bs)); flags are
# int32 0/1.  Mosaic then needs bu % 8 == 0 and bs % 128 == 0 (or the
# whole padded axis); the threshold rides in SMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _round_scan_kernel(sim, rank_r, rank_c, unresolved, is_rep, thr,
                       out_blocked, out_claimed):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_blocked[...] = jnp.zeros_like(out_blocked)
        out_claimed[...] = jnp.zeros_like(out_claimed)

    alpha = thr[0]
    s = sim[...]                                   # [bu, bs]
    pred = (s > 0.0) & (s >= alpha) & (rank_r[...] < rank_c[...])
    hit = lambda flag: jnp.max(jnp.where(pred & (flag[...] != 0), 1, 0),
                               axis=0, keepdims=True)
    out_blocked[...] = jnp.maximum(out_blocked[...], hit(unresolved))
    out_claimed[...] = jnp.maximum(out_claimed[...], hit(is_rep))


def _assign_kernel(sim, rank_r, is_rep, valid_c, thr,
                   out_w, out_rank, out_slot, *, bu: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_w[...] = jnp.zeros_like(out_w)
        out_rank[...] = jnp.full_like(out_rank, _BIG_RANK)
        out_slot[...] = jnp.full_like(out_slot, -1)

    alpha = thr[0]
    s = sim[...]                                   # [bu, bs]
    claim = ((is_rep[...] != 0) & (valid_c[...] != 0)
             & (s > 0.0) & (s >= alpha))
    w = jnp.where(claim, s, 0.0)
    loc_w = jnp.max(w, axis=0, keepdims=True)      # [1, bs]
    cand = claim & (w == loc_w) & (loc_w > 0.0)
    r = jnp.where(cand, rank_r[...], _BIG_RANK)
    loc_rank = jnp.min(r, axis=0, keepdims=True)
    # ranks are distinct, so the min-rank row is unique
    row = i * bu + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    loc_slot = jnp.min(jnp.where(cand & (r == loc_rank), row, _BIG_RANK),
                       axis=0, keepdims=True)

    acc_w = out_w[...]
    acc_rank = out_rank[...]
    # lexicographic (weight desc, visit rank asc) — ties across row blocks
    # resolve exactly like the full-matrix argmin in ref.claim_max_ref
    better = (loc_w > acc_w) | ((loc_w == acc_w) & (loc_rank < acc_rank))
    out_w[...] = jnp.where(better, loc_w, acc_w)
    out_rank[...] = jnp.where(better, loc_rank, acc_rank)
    out_slot[...] = jnp.where(better, loc_slot, out_slot[...])


def _specs(bu: int, bs: int):
    sim_spec = pl.BlockSpec((bu, bs), lambda j, i: (i, j))
    row_spec = pl.BlockSpec((bu, 1), lambda j, i: (i, 0))
    col_spec = pl.BlockSpec((1, bs), lambda j, i: (0, j))
    return sim_spec, row_spec, col_spec


def _row(v):
    return v.astype(jnp.int32).reshape(1, -1)


def _col(v):
    return v.astype(jnp.int32).reshape(-1, 1)


def _thr(alpha):
    return jnp.asarray(alpha, jnp.float32).reshape(1)


@functools.partial(jax.jit, static_argnames=("bu", "bs", "interpret"))
def round_scan_pallas(sim, rank, unresolved, is_rep, alpha, *,
                      bu: int = 8, bs: int = 128,
                      interpret: bool | None = None):
    """(blocked [S], claimed [S]) for one round; S divisible by bu and bs."""
    if interpret is None:
        interpret = default_interpret()
    S = sim.shape[0]
    assert sim.shape == (S, S) and S % bu == 0 and S % bs == 0, \
        (sim.shape, bu, bs)
    sim_spec, row_spec, col_spec = _specs(bu, bs)
    blocked, claimed = pl.pallas_call(
        _round_scan_kernel,
        grid=(S // bs, S // bu),
        in_specs=[sim_spec, row_spec, col_spec, row_spec, row_spec, _SMEM],
        out_specs=[col_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct((1, S), jnp.int32)] * 2,
        interpret=interpret,
    )(sim, _col(rank), _row(rank), _col(unresolved), _col(is_rep),
      _thr(alpha))
    return blocked[0] != 0, claimed[0] != 0


@functools.partial(jax.jit, static_argnames=("bu", "bs", "interpret"))
def assign_pallas(sim, rank, is_rep, valid, alpha, *,
                  bu: int = 8, bs: int = 128, interpret: bool | None = None):
    """(best_w [S], best_slot [S]) claim-max over representative rows."""
    if interpret is None:
        interpret = default_interpret()
    S = sim.shape[0]
    assert sim.shape == (S, S) and S % bu == 0 and S % bs == 0, \
        (sim.shape, bu, bs)
    sim_spec, row_spec, col_spec = _specs(bu, bs)
    w, _, slot = pl.pallas_call(
        functools.partial(_assign_kernel, bu=bu),
        grid=(S // bs, S // bu),
        in_specs=[sim_spec, row_spec, row_spec, col_spec, _SMEM],
        out_specs=[col_spec, col_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct((1, S), jnp.float32),
                   jax.ShapeDtypeStruct((1, S), jnp.int32),
                   jax.ShapeDtypeStruct((1, S), jnp.int32)],
        interpret=interpret,
    )(sim, _col(rank), _col(is_rep), _row(valid), _thr(alpha))
    w, slot = w[0], slot[0]
    return w, jnp.where(w > 0.0, slot, -1)


# ---------------------------------------------------------------------------
# Neighbor-list (top-K) kernels: the same two reductions on the sparse
# ``TopKSim`` rows.  The [S, S] matrix sweep becomes a [S, K] list sweep —
# one row-tile grid, no contraction axis (a slot's whole adjacency fits its
# K-entry list row).  The wrappers gather each neighbor's rank and round
# state into [S, K] operands with one XLA gather per round, so the kernels
# are pure row reductions.  O(S*K) HBM traffic per round.
# ---------------------------------------------------------------------------


def _topk_round_scan_kernel(ids, sims, nbr_rank, nbr_unres, nbr_rep,
                            rank_rows, thr, out_blocked, out_claimed):
    alpha = thr[0]
    v = sims[...]                                  # [bs, K]
    pred = ((ids[...] >= 0) & (v > 0.0) & (v >= alpha)
            & (nbr_rank[...] < rank_rows[...]))
    hit = lambda flag: jnp.max(jnp.where(pred & (flag[...] != 0), 1, 0),
                               axis=1, keepdims=True)
    out_blocked[...] = hit(nbr_unres)
    out_claimed[...] = hit(nbr_rep)


def _topk_assign_kernel(ids, sims, nbr_rank, nbr_rep, valid_rows, thr,
                        out_w, out_slot):
    alpha = thr[0]
    uid = ids[...]                                 # [bs, K]
    v = sims[...]
    claim = ((uid >= 0) & (valid_rows[...] != 0) & (v > 0.0)
             & (v >= alpha) & (nbr_rep[...] != 0))
    w = jnp.where(claim, v, 0.0)
    best_w = jnp.max(w, axis=1, keepdims=True)     # [bs, 1]
    cand = claim & (w == best_w) & (best_w > 0.0)
    r = jnp.where(cand, nbr_rank[...], _BIG_RANK)
    best_r = jnp.min(r, axis=1, keepdims=True)
    # ranks are distinct per slot, so the min-rank neighbor is unique
    slot = jnp.min(jnp.where(cand & (r == best_r), uid, _BIG_RANK), axis=1,
                   keepdims=True)
    out_w[...] = best_w
    out_slot[...] = jnp.where(best_w > 0.0, slot, -1)


def _topk_specs(bs: int, K: int):
    list_spec = pl.BlockSpec((bs, K), lambda i: (i, 0))
    row_spec = pl.BlockSpec((bs, 1), lambda i: (i, 0))
    return list_spec, row_spec


def _gather_rows(ids, *vals):
    """Each neighbor's entry of the per-slot vectors ``vals``: [S, K]."""
    safe = jnp.clip(ids, 0, ids.shape[0] - 1)
    return [v.astype(jnp.int32)[safe] for v in vals]


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def topk_round_scan_pallas(ids, sims, rank, unresolved, is_rep, alpha, *,
                           bs: int = 8, interpret: bool | None = None):
    """(blocked [S], claimed [S]) for one round; S divisible by bs."""
    if interpret is None:
        interpret = default_interpret()
    S, K = ids.shape
    assert S % bs == 0, (S, bs)
    list_spec, row_spec = _topk_specs(bs, K)
    blocked, claimed = pl.pallas_call(
        _topk_round_scan_kernel,
        grid=(S // bs,),
        in_specs=[list_spec] * 5 + [row_spec, _SMEM],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((S, 1), jnp.int32)] * 2,
        interpret=interpret,
    )(ids.astype(jnp.int32), sims,
      *_gather_rows(ids, rank, unresolved, is_rep), _col(rank), _thr(alpha))
    return blocked[:, 0] != 0, claimed[:, 0] != 0


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def topk_assign_pallas(ids, sims, rank, is_rep, valid, alpha, *,
                       bs: int = 8, interpret: bool | None = None):
    """(best_w [S], best_slot [S]) claim-max over neighbor lists."""
    if interpret is None:
        interpret = default_interpret()
    S, K = ids.shape
    assert S % bs == 0, (S, bs)
    list_spec, row_spec = _topk_specs(bs, K)
    w, slot = pl.pallas_call(
        _topk_assign_kernel,
        grid=(S // bs,),
        in_specs=[list_spec] * 4 + [row_spec, _SMEM],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((S, 1), jnp.int32)],
        interpret=interpret,
    )(ids.astype(jnp.int32), sims, *_gather_rows(ids, rank, is_rep),
      _col(valid), _thr(alpha))
    return w[:, 0], slot[:, 0]
