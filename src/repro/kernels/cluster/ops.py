"""Public wrappers for the round-parallel clustering kernels.

``plan_tiles`` resolves the tile geometry; the wrappers own the padding —
callers pass natural ``[S]`` / ``[S, S]`` operands and get ``[S]`` results
back, so the padding invariants (padded slots carry False state, zero
similarity, and fresh distinct ranks, and therefore join no reduction)
live in exactly one place.  On CPU the kernels run in interpret mode
(``repro.kernels.default_interpret``); the jnp oracle in ``ref.py`` is
the semantics they are tested against.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels.cluster.cluster import assign_pallas, round_scan_pallas


def plan_tiles(S: int, target_bu: int = 8, target_bs: int = 128):
    """(bu, bs, S_padded): row/column tile sizes and the padded slot count.

    Mirrors the stjoin convention (f32 (8, 128) register tiles); ``S`` is
    padded up to a common multiple so both tilings divide it.  The targets
    are taken verbatim (padding absorbs any S), so ``(target_bu,
    target_bs)`` IS the resolved geometry — ``EnginePlan.cluster_tiles``
    threads the pair here unchanged, and the autotuner
    (``repro.tune.autotune.tune_cluster_tiles``) sweeps it against the
    jnp oracle: any tile pair is bit-identical by the padding invariant
    (padded slots join no reduction), so tiles only move the
    VMEM-residency/grid-overhead trade-off, never the labels.
    """
    bu, bs = target_bu, target_bs
    q = math.lcm(bu, bs)
    return bu, bs, -(-S // q) * q


def _padded(sim, rank, vecs, bu: int, bs: int):
    """Pad the matrix, ranks, and bool state vectors to the tile multiple.

    Padded slots get zero similarity rows/columns, all-False state, and
    distinct out-of-range ranks — they contribute to no reduction and are
    sliced off by the callers.
    """
    S = sim.shape[0]
    _, _, Sp = plan_tiles(S, bu, bs)
    if Sp == S:
        return sim, rank, vecs
    sim_p = jnp.pad(sim, ((0, Sp - S), (0, Sp - S)))
    rank_p = jnp.concatenate(
        [rank.astype(jnp.int32), jnp.arange(S, Sp, dtype=jnp.int32)])
    vecs_p = [jnp.pad(v, (0, Sp - S), constant_values=False) for v in vecs]
    return sim_p, rank_p, vecs_p


def cluster_round_scan(sim, rank, unresolved, is_rep, alpha, *,
                       bu: int = 8, bs: int = 128, interpret: bool | None = None):
    """(blocked [S], claimed [S]) — one fused round scan."""
    S = sim.shape[0]
    sim_p, rank_p, (unres_p, rep_p) = _padded(
        sim, rank, [unresolved, is_rep], bu, bs)
    blocked, claimed = round_scan_pallas(
        sim_p, rank_p, unres_p, rep_p, alpha, bu=bu, bs=bs,
        interpret=interpret)
    return blocked[:S], claimed[:S]


def cluster_assign(sim, rank, is_rep, valid, alpha, *,
                   bu: int = 8, bs: int = 128, interpret: bool | None = None):
    """(best_w [S], best_slot [S]) — final claim-max over rep rows."""
    S = sim.shape[0]
    sim_p, rank_p, (rep_p, valid_p) = _padded(
        sim, rank, [is_rep, valid], bu, bs)
    w, slot = assign_pallas(sim_p, rank_p, rep_p, valid_p, alpha,
                            bu=bu, bs=bs, interpret=interpret)
    return w[:S], slot[:S]


def _padded_topk(ids, sims, rank, vecs, bs: int):
    """Pad neighbor-list operands to the row-tile multiple.

    Padded slots carry empty lists (``ids == -1``, zero sims), all-False
    state, and fresh distinct ranks, so they join no reduction and are
    sliced off by the callers.
    """
    S = ids.shape[0]
    Sp = -(-S // bs) * bs
    if Sp == S:
        return ids, sims, rank, vecs
    pad = Sp - S
    ids_p = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    sims_p = jnp.pad(sims, ((0, pad), (0, 0)))
    rank_p = jnp.concatenate(
        [rank.astype(jnp.int32), jnp.arange(S, Sp, dtype=jnp.int32)])
    vecs_p = [jnp.pad(v, (0, pad), constant_values=False) for v in vecs]
    return ids_p, sims_p, rank_p, vecs_p


def topk_cluster_round_scan(ids, sims, rank, unresolved, is_rep, alpha, *,
                            bs: int = 8, interpret: bool | None = None):
    """(blocked [S], claimed [S]) — one round scan over [S, K] lists."""
    from repro.kernels.cluster.cluster import topk_round_scan_pallas
    S = ids.shape[0]
    ids_p, sims_p, rank_p, (unres_p, rep_p) = _padded_topk(
        ids, sims, rank, [unresolved, is_rep], bs)
    blocked, claimed = topk_round_scan_pallas(
        ids_p, sims_p, rank_p, unres_p, rep_p, alpha, bs=bs,
        interpret=interpret)
    return blocked[:S], claimed[:S]


def topk_cluster_assign(ids, sims, rank, is_rep, valid, alpha, *,
                        bs: int = 8, interpret: bool | None = None):
    """(best_w [S], best_slot [S]) — claim-max over [S, K] lists."""
    from repro.kernels.cluster.cluster import topk_assign_pallas
    S = ids.shape[0]
    ids_p, sims_p, rank_p, (rep_p, valid_p) = _padded_topk(
        ids, sims, rank, [is_rep, valid], bs)
    w, slot = topk_assign_pallas(ids_p, sims_p, rank_p, rep_p, valid_p,
                                 alpha, bs=bs, interpret=interpret)
    return w[:S], slot[:S]
