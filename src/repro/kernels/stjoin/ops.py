"""Public jit'd wrappers: TrajectoryBatch-level subtrajectory join via Pallas.

These wrappers own the padding and the tile geometry, so the kernels in
``stjoin.py`` only ever see whole tiles.

* ``best_match_join_kernel``  — the dense materializing join: every (ref
  block, cand block) tile is visited.  Fallback and parity oracle.
* ``best_match_join_pruned``  — index-accelerated join: a spatiotemporal
  grid over tile bounding boxes (``repro.index.grid``) first emits, per
  reference block, the compacted list of candidate tiles that can contain
  a match; only those tiles are swept.  Output is bit-identical to the
  dense join (pruning is conservative).
* the fused streaming passes (``stjoin_vote_fused`` / ``stjoin_sim_fused``
  / ``stjoin_sim_panel_fused``), which never build the ``[T, M, C]`` cube.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.geometry import refine_join
from repro.core.types import JoinResult, TrajectoryBatch
from repro.index import grid as gridx
from repro.kernels.stjoin.stjoin import (
    stjoin_pallas,
    stjoin_sim_fused_flat,
    stjoin_sim_panel_flat,
    stjoin_vote_fused_flat,
)
from repro.utils.programs import run_program


def _pad_to(x: jnp.ndarray, mult: int, axis: int, fill):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _join_geometry(C: int, Mc: int, bc: int, bm: int):
    """Candidate tile ``bc`` and point chunk ``bm``, clamped to the data
    (a tile that spans the whole padded axis is always legal on TPU)."""
    return min(bc, C), min(bm, Mc)


def _padded_operands(ref: TrajectoryBatch, cand: TrajectoryBatch,
                     bp: int, bc: int, bm: int):
    """The materializing join's padding, shared with its index planner."""
    T, M = ref.x.shape
    rx = _pad_to(ref.x.reshape(-1), bp, 0, 0.0)
    ry = _pad_to(ref.y.reshape(-1), bp, 0, 0.0)
    rt = _pad_to(ref.t.reshape(-1), bp, 0, 0.0)
    rok = _pad_to(ref.valid.reshape(-1), bp, 0, False)
    rid = _pad_to(
        jnp.broadcast_to(ref.traj_id[:, None], (T, M)).reshape(-1), bp, 0, -1)

    cx = _pad_to(_pad_to(cand.x, bm, 1, 0.0), bc, 0, 0.0)
    cy = _pad_to(_pad_to(cand.y, bm, 1, 0.0), bc, 0, 0.0)
    ct = _pad_to(_pad_to(cand.t, bm, 1, 0.0), bc, 0, 0.0)
    cok = _pad_to(_pad_to(cand.valid, bm, 1, False), bc, 0, False)
    cid = _pad_to(cand.traj_id, bc, 0, -2)
    return (rx, ry, rt, rid, rok), (cx, cy, ct, cid, cok)


@functools.partial(jax.jit, static_argnames=("bp", "bc", "bm", "interpret"))
def _join_sweep(ref, cand, eps_sp, eps_t, tile_ids, *, bp, bc, bm,
                interpret):
    T, M = ref.x.shape
    C = cand.x.shape[0]
    ref_ops, cand_ops = _padded_operands(ref, cand, bp, bc, bm)
    w, idx = stjoin_pallas(*ref_ops, *cand_ops, eps_sp, eps_t, tile_ids,
                           bp=bp, bc=bc, bm=bm, interpret=interpret)
    return JoinResult(best_w=w[:T * M, :C].reshape(T, M, C),
                      best_idx=idx[:T * M, :C].reshape(T, M, C))


def best_match_join_kernel(ref: TrajectoryBatch, cand: TrajectoryBatch,
                           eps_sp, eps_t, *, bp=256, bc=128, bm=128,
                           interpret: bool | None = None) -> JoinResult:
    """Dense Pallas join: ``JoinResult [T, M, C]``."""
    bc, bm = _join_geometry(cand.x.shape[0], cand.x.shape[1], bc, bm)
    return _join_sweep(ref, cand, eps_sp, eps_t, None, bp=bp, bc=bc, bm=bm,
                       interpret=interpret)


def plan_join_index(ref: TrajectoryBatch, cand: TrajectoryBatch,
                    eps_sp, eps_t, *, bp=256, bc=128, use_cells: bool = True):
    """Candidate-tile mask + per-ref-block survivor counts.

    Host-driven (not jitted as a whole: the grid geometry is fitted from
    the concrete data, and baking it in as a static jit argument would
    retrace on every new batch).  The array math inside is plain jnp.
    ``bc`` is the resolved candidate tile.
    Returns ``(mask [nRb, nCb] bool, counts [nRb] i32, spec | None)``.
    """
    (rx, ry, rt, _, rok), (cx, cy, ct, _, cok) = _padded_operands(
        ref, cand, bp, bc, 1)
    rboxes = gridx.point_block_boxes(rx, ry, rt, rok, bp)
    cboxes = gridx.traj_block_boxes(cx, cy, ct, cok, bc)
    spec = None
    if use_cells:
        spec = gridx.fit_grid(cboxes, float(eps_sp), float(eps_t))
        table = gridx.build_cell_table(spec, cboxes)
        mask = gridx.candidate_tile_mask(
            spec, table, rboxes, cboxes, eps_sp, eps_t)
    else:
        mask = gridx.exact_pair_mask(rboxes, cboxes, eps_sp, eps_t)
    counts = jnp.sum(mask, axis=1).astype(jnp.int32)
    return mask, counts, spec


def best_match_join_pruned(ref: TrajectoryBatch, cand: TrajectoryBatch,
                           eps_sp, eps_t, *, bp=256, bc=128, bm=128,
                           max_tiles: int | None = None,
                           use_cells: bool = True,
                           interpret: bool | None = None,
                           return_stats: bool = False):
    """Index-pruned best-match join; bit-identical to the dense kernel.

    Host-driven planning (concrete inputs required): fits the eps-derived
    grid, compacts the surviving candidate-tile lists to a static width
    ``K`` (``max_tiles`` or the observed maximum), then sweeps only those
    tiles.  Raises if ``max_tiles`` is too small to keep every survivor,
    since dropping one would break parity.
    """
    bc, bm = _join_geometry(cand.x.shape[0], cand.x.shape[1], bc, bm)
    mask, counts, _ = plan_join_index(
        ref, cand, eps_sp, eps_t, bp=bp, bc=bc, use_cells=use_cells)

    need = gridx.plan_max_tiles(counts)
    K = max_tiles if max_tiles is not None else need
    if int(np.max(np.asarray(counts), initial=0)) > K:
        raise ValueError(
            f"max_tiles={K} drops candidate tiles (need {need}); "
            "the pruned join would no longer match the dense join")
    tile_ids, counts = gridx.compact_candidates(mask, K)
    out = run_program(_join_sweep, ref, cand, eps_sp, eps_t, tile_ids,
                      bp=bp, bc=bc, bm=bm, interpret=interpret)
    if return_stats:
        return out, gridx.prune_stats(counts, mask.shape[1])
    return out


# ---------------------------------------------------------------------------
# Fused streaming join (epilogue fusion): the [T, M, C] JoinResult cube is
# never materialized.  Pass 1 (``stjoin_vote_fused``) returns the per-point
# vote sums and the bit-packed TSA2 neighbor words; pass 2
# (``stjoin_sim_fused``) re-sweeps the same tiles after segmentation and
# accumulates refined weights straight into the raw similarity blocks.
# Both accept an optional pre-computed ``tile_ids`` plan (``plan_fused_tiles``)
# to sweep only the index-surviving candidate tiles.
# ---------------------------------------------------------------------------


def _fused_geometry(T: int, M: int, C: int, Mc: int, rows: int | None,
                    bc: int, bm: int):
    """Resolve the fused kernels' tile geometry for raw [T, M]/[C, Mc] data.

    Ref blocks hold whole trajectory rows (in-kernel delta_t refine), so a
    block is ``rows`` rows of ``M`` points, about 256 points by default.
    ``bc`` divides 32 or is a multiple of 32 (a block's bits pack into
    whole words) and is clamped to the candidates padded to 32; ``bm``
    (the candidate-point padding granule) is clamped to the row length.
    Returns ``(rows, bc, bm, padC, mc_pad)``.
    """
    rows = rows if rows is not None else max(1, 256 // max(M, 1))
    rows = min(rows, max(T, 1))
    c32 = -(-max(C, 1) // 32) * 32
    bc = (max(d for d in (1, 2, 4, 8, 16) if d <= max(bc, 1)) if bc < 32
          else min(bc // 32 * 32, c32))
    padC = (-C) % max(32, bc)
    bm = min(bm, Mc)
    return rows, bc, bm, padC, (-Mc) % bm


def _fused_ref_operands(rx, ry, rt, rvalid, rid, rows: int):
    """Pad to whole ref blocks and flatten row-major (rows stay contiguous)."""
    T, M = rx.shape
    padT = (-T) % rows
    pad2 = lambda a, f: jnp.pad(a, ((0, padT), (0, 0)), constant_values=f)
    rid_full = jnp.broadcast_to(rid[:, None], (T, M))
    return (pad2(rx, 0.0).reshape(-1), pad2(ry, 0.0).reshape(-1),
            pad2(rt, 0.0).reshape(-1),
            pad2(rid_full.astype(jnp.int32), -1).reshape(-1),
            pad2(rvalid, False).reshape(-1))


def _fused_cand_operands(cx, cy, ct, cvalid, cid, padC: int, mc_pad: int):
    """Pad candidates to whole tiles and point granules, in kernel operand
    order ``(x, y, t, id, ok)``."""
    pad = lambda a, f: jnp.pad(a, ((0, padC), (0, mc_pad)), constant_values=f)
    return (pad(cx, 0.0), pad(cy, 0.0), pad(ct, 0.0),
            jnp.pad(cid.astype(jnp.int32), (0, padC), constant_values=-2),
            pad(cvalid, False))


class FusedTilePlan(NamedTuple):
    """A candidate-tile plan bound to the geometry it was built for.

    ``tile_ids`` column values index candidate *blocks of ``bc`` rows*, so
    reusing a plan under a different geometry would silently mis-address
    candidates — the fused entry points therefore verify these fields
    against their own resolved geometry before sweeping.
    """

    tile_ids: jnp.ndarray     # [nRb, K] int32, -1 padded, ascending
    rows: int
    bc: int
    bm: int


def _resolve_plan(tile_ids, rows: int, bc: int, bm: int):
    """Unpack a FusedTilePlan (geometry-checked) or pass a raw array."""
    if tile_ids is None:
        return None
    if isinstance(tile_ids, FusedTilePlan):
        if (tile_ids.rows, tile_ids.bc, tile_ids.bm) != (rows, bc, bm):
            raise ValueError(
                f"tile plan was built for geometry rows={tile_ids.rows}, "
                f"bc={tile_ids.bc}, bm={tile_ids.bm} but the sweep resolved "
                f"rows={rows}, bc={bc}, bm={bm}; candidate blocks would be "
                "mis-addressed")
        return tile_ids.tile_ids
    return tile_ids


def plan_fused_tiles(rx, ry, rt, rvalid, cx, cy, ct, cvalid, eps_sp, eps_t,
                     *, rows: int | None = None, bc: int = 128, bm: int = 128,
                     use_cells: bool = True, max_tiles: int | None = None):
    """Host-driven candidate-tile plan for the fused kernels.

    Same two-stage conservative pruning as ``plan_join_index`` but on raw
    ``[T, M]`` / ``[C, Mc]`` arrays with the fused row-aligned block
    geometry.  Returns a ``FusedTilePlan`` (tile ids -1 padded, ascending,
    plus the resolved geometry) ready for the fused entry points, which
    reject a plan whose geometry differs from their own.  Raises if
    ``max_tiles`` would drop a survivor.

    Geometry knobs (``rows``, ``bc``, ``bm``) are the fused tile plan of
    ``EnginePlan.fused_tiles`` (DESIGN.md §9): ``rows`` reference-trajectory
    rows per block (``None`` = about 256 points per block), ``bc``
    candidate trajectories per block (the lane tile: on TPU a multiple of
    128 or every candidate), ``bm`` the candidate-point padding granule.
    Pruning quality depends on them — smaller blocks give the grid tighter
    boxes to reject, larger blocks amortize sweep overhead — which is why
    the dispatcher re-binds the *resolved* geometry into the plan before
    tracing: the sweep must run the exact tiling the tile ids were built
    for.  The autotuner (``repro.tune.autotune.tune_join``) sweeps this
    lattice rather than guessing.
    """
    M = rx.shape[1]
    rows, bc, bm, padC, mc_pad = _fused_geometry(
        rx.shape[0], M, cx.shape[0], cx.shape[1], rows, bc, bm)
    bp = rows * M
    frx, fry, frt, _, frok = _fused_ref_operands(
        rx, ry, rt, rvalid, jnp.zeros((rx.shape[0],), jnp.int32), rows)
    fcx, fcy, fct, _, fcok = _fused_cand_operands(
        cx, cy, ct, cvalid, jnp.zeros((cx.shape[0],), jnp.int32), padC,
        mc_pad)

    rboxes = gridx.point_block_boxes(frx, fry, frt, frok, bp)
    cboxes = gridx.traj_block_boxes(fcx, fcy, fct, fcok, bc)
    if use_cells:
        spec = gridx.fit_grid(cboxes, float(eps_sp), float(eps_t))
        table = gridx.build_cell_table(spec, cboxes)
        mask = gridx.candidate_tile_mask(
            spec, table, rboxes, cboxes, eps_sp, eps_t)
    else:
        mask = gridx.exact_pair_mask(rboxes, cboxes, eps_sp, eps_t)
    counts = jnp.sum(mask, axis=1).astype(jnp.int32)
    need = gridx.plan_max_tiles(counts)
    # K >= 1 even when nothing survives: a zero-width slot axis would give
    # the kernels an empty grid and leave their accumulators uninitialized
    K = max(max_tiles, 1) if max_tiles is not None else need
    if int(np.max(np.asarray(counts), initial=0)) > K:
        raise ValueError(
            f"max_tiles={K} drops candidate tiles (need {need}); "
            "the fused pruned sweep would no longer match the dense sweep")
    tile_ids, _ = gridx.compact_candidates(mask, K)
    return FusedTilePlan(tile_ids=tile_ids, rows=rows, bc=bc, bm=bm)


def stjoin_vote_fused_arrays(rx, ry, rt, rvalid, rid, cx, cy, ct, cvalid,
                             cid, eps_sp, eps_t, delta_t=0.0, *,
                             rows: int | None = None, bc: int = 128,
                             bm: int = 128, tile_ids=None,
                             with_masks: bool = True,
                             interpret: bool | None = None):
    """Fused pass 1 on raw arrays: ``(vote [T, M], words [T, M, ceil(C/32)])``.

    Subsumes ``voting.point_voting`` and ``voting.neighbor_mask_packed``
    over a delta_t-refined join without materializing it.  ``tile_ids``
    (from ``plan_fused_tiles`` with identical geometry) switches to the
    index-pruned sweep; identical output either way.  ``with_masks=False``
    (segmentation won't consume neighbor sets, i.e. TSA1) returns
    ``(vote, None)`` and skips the packed-word accumulator entirely.
    """
    T, M = rx.shape
    C, Mc = cx.shape
    rows, bc, bm, padC, mc_pad = _fused_geometry(T, M, C, Mc, rows, bc, bm)
    tile_ids = _resolve_plan(tile_ids, rows, bc, bm)
    vote, words = stjoin_vote_fused_flat(
        *_fused_ref_operands(rx, ry, rt, rvalid, rid, rows),
        *_fused_cand_operands(cx, cy, ct, cvalid, cid, padC, mc_pad),
        eps_sp, eps_t, delta_t, tile_ids, rows=rows, M=M, bc=bc,
        with_words=with_masks, interpret=interpret)
    vote = vote[:T * M].reshape(T, M)
    if words is None:
        return vote, None
    W = -(-C // 32)
    return vote, words[:T * M].reshape(T, M, -1)[:, :, :W]


def stjoin_vote_fused(ref: TrajectoryBatch, cand: TrajectoryBatch,
                      eps_sp, eps_t, delta_t=0.0, *, use_index: bool = False,
                      use_cells: bool = True, max_tiles: int | None = None,
                      rows: int | None = None, bc: int = 128, bm: int = 128,
                      with_masks: bool = True,
                      interpret: bool | None = None):
    """Batch-level fused pass 1 (vote sums + packed neighbor words)."""
    tile_ids = None
    if use_index:
        tile_ids = plan_fused_tiles(
            ref.x, ref.y, ref.t, ref.valid, cand.x, cand.y, cand.t,
            cand.valid, eps_sp, eps_t, rows=rows, bc=bc, bm=bm,
            use_cells=use_cells, max_tiles=max_tiles)
    return stjoin_vote_fused_arrays(
        ref.x, ref.y, ref.t, ref.valid, ref.traj_id, cand.x, cand.y,
        cand.t, cand.valid, cand.traj_id, eps_sp, eps_t, delta_t,
        rows=rows, bc=bc, bm=bm, tile_ids=tile_ids,
        with_masks=with_masks, interpret=interpret)


def _sim_operands(rx, ry, rt, rvalid, rid, ref_sub, cx, cy, ct, cvalid, cid,
                  cand_sub, rows, bc, bm):
    """Padded kernel operands plus the resolved geometry for pass 2.

    Pass 2 packs no words, so its candidate axis is padded to whole
    ``bc`` tiles only, not to 32 like pass 1's.
    """
    T, M = rx.shape
    C, Mc = cx.shape
    rows, bc, bm, _, mc_pad = _fused_geometry(T, M, C, Mc, rows, bc, bm)
    padC = (-C) % bc
    padT = (-T) % rows
    rsub = jnp.pad(ref_sub.astype(jnp.int32), ((0, padT), (0, 0)),
                   constant_values=-1).reshape(-1)
    csub = jnp.pad(cand_sub.astype(jnp.int32), ((0, padC), (0, mc_pad)),
                   constant_values=-1)
    return ((_fused_ref_operands(rx, ry, rt, rvalid, rid, rows), rsub,
             _fused_cand_operands(cx, cy, ct, cvalid, cid, padC, mc_pad),
             csub), (rows, bc, bm))


def stjoin_sim_fused_arrays(rx, ry, rt, rvalid, rid, ref_sub, cx, cy, ct,
                            cvalid, cid, cand_sub, max_subs: int,
                            eps_sp, eps_t, delta_t=0.0, *,
                            rows: int | None = None, bc: int = 128,
                            bm: int = 128, tile_ids=None,
                            interpret: bool | None = None):
    """Fused pass 2 on raw arrays: raw similarity scatter
    ``[T * max_subs, C * max_subs]``.

    ``ref_sub [T, M]`` / ``cand_sub [C, Mc]``: each point's local sub-slot
    (-1 = unsegmented).  Row ``r * max_subs + a`` / column ``c * max_subs
    + k`` is slot ``a`` of ref row ``r`` / slot ``k`` of candidate ``c``.
    Subsumes the materializing ``similarity_matrix`` gather/scatter over
    T*M*C elements; normalization is left to ``similarity.finalize_sim``
    so both paths share the math.
    """
    T, M = rx.shape
    C = cx.shape[0]
    (ref_ops, rsub, cand_ops, csub), (rows, bc, bm) = _sim_operands(
        rx, ry, rt, rvalid, rid, ref_sub, cx, cy, ct, cvalid, cid, cand_sub,
        rows, bc, bm)
    raw = stjoin_sim_fused_flat(
        ref_ops, rsub, cand_ops, csub, eps_sp, eps_t, delta_t,
        _resolve_plan(tile_ids, rows, bc, bm), rows=rows, M=M, ms=max_subs,
        bc=bc, interpret=interpret)
    return raw[:T * max_subs, :C * max_subs]


def stjoin_sim_fused(ref: TrajectoryBatch, cand: TrajectoryBatch,
                     ref_sub_local, cand_sub_local, max_subs: int,
                     eps_sp, eps_t, delta_t=0.0, *, tile_ids=None,
                     rows: int | None = None, bc: int = 128, bm: int = 128,
                     interpret: bool | None = None):
    """Batch-level fused pass 2: un-normalized ``raw [S_ref, S_cand]``.

    Slot maps mirror ``similarity_matrix``: ref point (r, m) scatters into
    row ``r * max_subs + sub_local[r, m]``; the matched candidate point
    (c, best_idx) into column ``c * max_subs + cand_sub_local[c, idx]``.
    """
    return stjoin_sim_fused_arrays(
        ref.x, ref.y, ref.t, ref.valid, ref.traj_id, ref_sub_local,
        cand.x, cand.y, cand.t, cand.valid, cand.traj_id, cand_sub_local,
        max_subs, eps_sp, eps_t, delta_t, rows=rows, bc=bc, bm=bm,
        tile_ids=tile_ids, interpret=interpret)


def stjoin_sim_panel_fused(ref: TrajectoryBatch, cand: TrajectoryBatch,
                           ref_sub_local, cand_sub_local, max_subs: int,
                           eps_sp, eps_t, delta_t=0.0, *, p0, panel: int,
                           tile_ids=None, rows: int | None = None,
                           bc: int = 128, bm: int = 128,
                           interpret: bool | None = None):
    """Panel-streamed fused pass 2: one ``Sb``-row panel of the raw
    similarity scatter in both orientations.

    Returns ``(fwd [panel, S_cand], rev [panel, S_ref])`` where
    ``fwd[i, j] = raw[p0 + i, j]`` and ``rev[i, j] = raw[j, p0 + i]`` of
    the accumulator ``stjoin_sim_fused`` would build — bit-equal cell
    sums, panel rows only.  ``p0`` may be traced (the panel loop re-invokes
    one trace); ``panel`` is static.  ``tile_ids`` (from
    ``plan_fused_tiles`` with identical geometry) restricts the forward
    sweep to the index-surviving tiles; identical output either way.
    The slabs feed ``repro.core.similarity.topk_stream``'s panel
    finalization.
    """
    T, M = ref.x.shape
    C = cand.x.shape[0]
    (ref_ops, rsub, cand_ops, csub), (rows, bc, bm) = _sim_operands(
        ref.x, ref.y, ref.t, ref.valid, ref.traj_id, ref_sub_local,
        cand.x, cand.y, cand.t, cand.valid, cand.traj_id, cand_sub_local,
        rows, bc, bm)
    fwd, rev = stjoin_sim_panel_flat(
        ref_ops, rsub, cand_ops, csub, eps_sp, eps_t, delta_t, p0,
        _resolve_plan(tile_ids, rows, bc, bm), rows=rows, M=M, ms=max_subs,
        panel=panel, bc=bc, interpret=interpret)
    return fwd[:, :C * max_subs], rev[:, :T * max_subs]


def subtrajectory_join(ref: TrajectoryBatch, cand: TrajectoryBatch,
                       eps_sp, eps_t, delta_t=0.0, *, use_index: bool = False,
                       **kw) -> JoinResult:
    """Kernel-backed Problem 1 (join + delta_t refine).

    ``use_index=True`` routes through the grid-pruned kernel (requires
    concrete inputs for the host-side planning pass); output is identical.
    """
    if use_index:
        j = best_match_join_pruned(ref, cand, eps_sp, eps_t, **kw)
    else:
        j = best_match_join_kernel(ref, cand, eps_sp, eps_t, **kw)
    return refine_join(j, ref.t, delta_t)
