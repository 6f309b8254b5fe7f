"""Public jit'd wrappers: TrajectoryBatch-level subtrajectory join via Pallas.

These wrappers own the padding and the tile geometry, so the kernels in
``stjoin.py`` only ever see whole tiles.

* ``best_match_join_kernel``  — the dense materializing join: every (ref
  block, cand block) tile is visited.  Fallback and parity oracle.
  With ``delta_t > 0`` DTJ's run refine runs in the kernel and the
  matches are counted before and after it.
* ``best_match_join_pruned``  — index-accelerated join: a spatiotemporal
  grid over tile bounding boxes (``repro.index.grid``) first emits, per
  reference block, the compacted list of candidate tiles that can contain
  a match; only those tiles are swept.  Output is bit-identical to the
  dense join (pruning is conservative).
* the fused streaming passes (``stjoin_vote_fused`` / ``stjoin_sim_fused``
  / ``stjoin_sim_panel_fused``), which never build the ``[T, M, C]`` cube.
* ``stjoin_sim_cube``  — pass 2 where the cube exists: the raw similarity
  of ``stjoin_sim_fused``, bit for bit, read from the join's tiles.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.types import JoinResult, TrajectoryBatch
from repro.index import grid as gridx
from repro.kernels.stjoin.stjoin import (
    stjoin_dtj_pallas,
    stjoin_pallas,
    stjoin_sim_fused_flat,
    stjoin_sim_fused_flat_cube,
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
    """The materializing join's padding."""
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


def _row_block(T: int, M: int, bp: int, delta_t) -> int:
    """The join's reference block: ``bp`` points, or, with the delta_t
    refine, as many whole rows of ``M`` as fit in ``bp`` (at least one, at
    most ``T``), so that a run never spans two blocks."""
    if float(delta_t) <= 0:
        return bp
    return max(1, min(bp // max(M, 1), T)) * M


@functools.partial(jax.jit,
                   static_argnames=("bp", "bc", "bm", "delta_t", "interpret"))
def _join_sweep(ref, cand, eps_sp, eps_t, tile_ids, *, bp, bc, bm, delta_t,
                interpret):
    """``(JoinResult [T, M, C], matches)``: the Pallas join, refined in the
    kernel when ``delta_t > 0`` (``bp`` whole rows, ``_row_block``), with
    ``matches`` ``[2]`` i32 the count before and after the refine, else
    None."""
    T, M = ref.x.shape
    C = cand.x.shape[0]
    ref_ops, cand_ops = _padded_operands(ref, cand, bp, bc, bm)
    matches = None
    if delta_t > 0:
        w, idx, matches = stjoin_dtj_pallas(
            *ref_ops, *cand_ops, eps_sp, eps_t, delta_t, tile_ids,
            rows=bp // M, M=M, bc=bc, bm=bm, interpret=interpret)
    else:
        w, idx = stjoin_pallas(*ref_ops, *cand_ops, eps_sp, eps_t, tile_ids,
                               bp=bp, bc=bc, bm=bm, interpret=interpret)
    return JoinResult(best_w=w[:T * M, :C].reshape(T, M, C),
                      best_idx=idx[:T * M, :C].reshape(T, M, C)), matches


def best_match_join_kernel(ref: TrajectoryBatch, cand: TrajectoryBatch,
                           eps_sp, eps_t, delta_t=0.0, *, bp=256, bc=128,
                           bm=128, interpret: bool | None = None):
    """Dense Pallas join: ``(JoinResult [T, M, C], matches)``, refined in
    the kernel when ``delta_t`` (concrete) is above 0, ``matches`` then
    the ``[2]`` i32 count of matches before and after the refine on the
    device, else None (see ``_join_sweep``)."""
    bc, bm = _join_geometry(cand.x.shape[0], cand.x.shape[1], bc, bm)
    return _join_sweep(ref, cand, eps_sp, eps_t, None,
                       bp=_row_block(*ref.x.shape, bp, delta_t), bc=bc,
                       bm=bm, delta_t=float(delta_t), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bp", "bc", "pad_c"))
def _plan_boxes(rx, ry, rt, rok, cx, cy, ct, cok, *, bp, bc, pad_c):
    """Both sides' block boxes for one planning: the ``[T, M]`` reference
    points flattened and padded to blocks of ``bp``, the ``[C, Mc]``
    candidate rows padded by ``pad_c`` rows, in blocks of ``bc`` rows.
    Padding is invalid, so it never widens a box."""
    ref = lambda a, fill: _pad_to(a.reshape(-1), bp, 0, fill)
    cand = lambda a, fill: jnp.pad(a, ((0, pad_c), (0, 0)),
                                   constant_values=fill)
    return (gridx.point_block_boxes(ref(rx, 0.0), ref(ry, 0.0), ref(rt, 0.0),
                                    ref(rok, False), bp),
            gridx.traj_block_boxes(cand(cx, 0.0), cand(cy, 0.0),
                                   cand(ct, 0.0), cand(cok, False), bc))


@jax.jit
def _plan_grid(cboxes, eps_sp, eps_t):
    """The grid fitted to the candidate blocks, and their center cells."""
    grid = gridx.fit_grid_arrays(cboxes, eps_sp, eps_t)
    return grid, gridx.cell_coords(grid, cboxes)


@jax.jit
def _plan_mask(rboxes, cboxes, eps_sp, eps_t, grid, coords):
    """The candidate-tile mask (the coarse cell test and the exact test,
    or the exact test alone when ``grid`` is None) and its row counts."""
    if grid is None:
        mask = gridx.exact_pair_mask(rboxes, cboxes, eps_sp, eps_t)
    else:
        mask = gridx.candidate_tile_mask(grid, coords, rboxes, cboxes,
                                         eps_sp, eps_t)
    return mask, jnp.sum(mask, axis=1).astype(jnp.int32)


_plan_compact = jax.jit(gridx.compact_candidates,
                        static_argnames="max_tiles")


def _tile_mask(ref_pts, cand_rows, bp: int, bc: int, pad_c: int, eps_sp,
               eps_t, use_cells: bool):
    """The grid index over one planning's operands: ``ref_pts``
    ``(x, y, t, ok)`` ``[T, M]`` in blocks of ``bp`` points, ``cand_rows``
    ``(x, y, t, ok)`` ``[C, Mc]`` padded by ``pad_c`` rows, in blocks of
    ``bc`` rows.

    Block boxes, then the grid fitted to them and the candidates' center
    cells (with ``use_cells``), then the candidate-tile mask: each one
    compiled program in its host span, ``eps_sp``/``eps_t`` traced, so a
    new batch of the same shape compiles nothing.  Nothing comes to the
    host.  Returns ``(mask [nRb, nCb] bool, counts [nRb] i32,
    grid | None)``, the grid a ``GridFit`` on the device.
    """
    with TraceAnnotation("index.boxes"):
        rboxes, cboxes = _plan_boxes(*ref_pts, *cand_rows, bp=bp, bc=bc,
                                     pad_c=pad_c)
    grid = coords = None
    if use_cells:
        with TraceAnnotation("index.grid"):
            grid, coords = _plan_grid(cboxes, eps_sp, eps_t)
    with TraceAnnotation("index.mask"):
        mask, counts = _plan_mask(rboxes, cboxes, eps_sp, eps_t, grid,
                                  coords)
    return mask, counts, grid


def _compact_tiles(mask, counts, K: int | None):
    """Compact ``mask`` to each row block's surviving tile ids, ``K`` wide
    (``None``: the widest list).  ``counts`` comes to the host once, the
    planning's one host copy, for the width, the check that ``K`` drops no
    tile, and the kept count; the compaction is one program a width.
    Returns ``(tile_ids [nRb, K], counts, K, kept)``."""
    with TraceAnnotation("index.compact"):
        host = np.asarray(counts)
        need = gridx.plan_max_tiles(host)
        K = need if K is None else K
        if int(np.max(host, initial=0)) > K:
            raise ValueError(
                f"max_tiles={K} drops candidate tiles (need {need}); the "
                "pruned sweep would no longer match the dense sweep")
        tile_ids, counts = _plan_compact(mask, max_tiles=K)
    return tile_ids, counts, K, int(host.sum())


def plan_join_index(ref: TrajectoryBatch, cand: TrajectoryBatch,
                    eps_sp, eps_t, *, bp=256, bc=128, use_cells: bool = True):
    """Candidate-tile mask + per-ref-block survivor counts.

    The materializing join's block geometry: reference points in blocks
    of ``bp``, candidates in blocks of ``bc`` rows (the resolved tile).
    The planning's steps are compiled programs that fit the grid on the
    device (see ``_tile_mask``); nothing comes to the host here, and the
    one host copy is the caller's, of ``counts``, for the width ``K``.
    Returns ``(mask [nRb, nCb] bool, counts [nRb] i32, grid | None)``,
    ``grid`` the fitted ``GridFit`` (None without ``use_cells``).
    """
    return _tile_mask((ref.x, ref.y, ref.t, ref.valid),
                      (cand.x, cand.y, cand.t, cand.valid), bp, bc,
                      (-cand.x.shape[0]) % bc, eps_sp, eps_t, use_cells)


def best_match_join_pruned(ref: TrajectoryBatch, cand: TrajectoryBatch,
                           eps_sp, eps_t, delta_t=0.0, *, bp=256, bc=128,
                           bm=128, max_tiles: int | None = None,
                           use_cells: bool = True,
                           interpret: bool | None = None,
                           return_stats: bool = False):
    """Index-pruned best-match join; bit-identical to the dense kernel,
    ``(JoinResult, matches)`` as ``best_match_join_kernel`` (with
    ``return_stats``, ``((JoinResult, matches), stats)``).

    Plans on the device (concrete inputs required): fits the eps-derived
    grid, compacts the surviving candidate-tile lists to a static width
    ``K`` (``max_tiles`` or the observed maximum, which needs the one host
    copy of the survivor counts), then sweeps only those tiles.  Raises if
    ``max_tiles`` is too small to keep every survivor, since dropping one
    would break parity.  The planning is the host span
    ``index.plan.join``, with the kept tiles, all tiles and ``K``.
    """
    bc, bm = _join_geometry(cand.x.shape[0], cand.x.shape[1], bc, bm)
    bp = _row_block(*ref.x.shape, bp, delta_t)
    with TraceAnnotation("index.plan.join") as span:
        mask, counts, _ = plan_join_index(
            ref, cand, eps_sp, eps_t, bp=bp, bc=bc, use_cells=use_cells)
        tile_ids, counts, K, kept = _compact_tiles(mask, counts, max_tiles)
        if TraceAnnotation.is_enabled():
            span.set_metadata(kept=kept, total=mask.size, K=K)
    out = run_program(_join_sweep, ref, cand, eps_sp, eps_t, tile_ids,
                      bp=bp, bc=bc, bm=bm, delta_t=float(delta_t),
                      interpret=interpret)
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
    """Candidate-tile plan for the fused kernels.

    Same two-stage conservative pruning as ``plan_join_index``, the same
    compiled steps and one host copy, but on raw ``[T, M]`` / ``[C, Mc]``
    arrays with the fused row-aligned block geometry (a reference block is
    ``rows`` whole rows).  Returns a ``FusedTilePlan`` (tile ids -1
    padded, ascending, plus the resolved geometry) ready for the fused
    entry points, which reject a plan whose geometry differs from their
    own.  Raises if ``max_tiles`` would drop a survivor.

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

    The planning is the host span ``index.plan.sweep``, with the kept
    tiles, all tiles, ``K`` and the resolved ``rows`` and ``bc``.
    """
    with TraceAnnotation("index.plan.sweep") as span:
        M = rx.shape[1]
        rows, bc, bm, padC, _ = _fused_geometry(
            rx.shape[0], M, cx.shape[0], cx.shape[1], rows, bc, bm)
        mask, counts, _ = _tile_mask((rx, ry, rt, rvalid),
                                     (cx, cy, ct, cvalid), rows * M, bc, padC,
                                     eps_sp, eps_t, use_cells)
        # K >= 1 even when nothing survives: a zero-width slot axis would
        # give the kernels an empty grid and leave their accumulators
        # uninitialized
        tile_ids, _, K, kept = _compact_tiles(
            mask, counts, None if max_tiles is None else max(max_tiles, 1))
        if TraceAnnotation.is_enabled():
            span.set_metadata(kept=kept, total=mask.size, K=K, rows=rows,
                              bc=bc)
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


def cand_starts(cand_sub, max_subs: int):
    """``[C, Mc]`` candidate sub-slots (-1 on invalid points) -> ``[max_subs,
    C]`` i32: row ``a`` holds each candidate's first point of sub-slot
    ``a`` or more, ``Mc`` where it has none.  The boundaries from which
    ``stjoin_sim_fused_flat_cube`` maps a matched point to its sub-slot."""
    Mc = cand_sub.shape[1]
    a = jnp.arange(max_subs, dtype=jnp.int32)[:, None, None]
    m = jnp.arange(Mc, dtype=jnp.int32)
    return jnp.min(jnp.where(cand_sub[None] >= a, m, Mc), axis=2).astype(
        jnp.int32)


def stjoin_sim_cube(join: JoinResult, ref_sub_local, cand_sub_local,
                    max_subs: int, *, tile_ids=None,
                    rows: int | None = None, bc: int = 128, bm: int = 128,
                    interpret: bool | None = None):
    """Pass 2 from a materialized join: ``stjoin_sim_fused``'s raw
    ``[T * max_subs, C * max_subs]``, bit for bit, read from the kept tiles
    of ``join`` (``[T, M, C]``, DTJ's refine applied, as
    ``subtrajectory_join`` returns it) instead of re-running the pair
    tests.  Sub-slots as ``segmentation`` builds them: nondecreasing
    along each row's valid points, -1 elsewhere.  The geometry and
    ``tile_ids`` are the sweep's (``plan_fused_tiles``).

    The kernel reads the cube's ``[T * M, C]`` view, a bitcast; only a
    batch whose ``T`` or ``C`` leaves a ragged last tile pads a copy.
    """
    T, M, C = join.best_w.shape
    rows, bc, bm, _, _ = _fused_geometry(T, M, C, cand_sub_local.shape[1],
                                         rows, bc, bm)

    def view(a, fill):
        return _pad_to(_pad_to(a.reshape(T * M, C), rows * M, 0, fill),
                       bc, 1, fill)

    rsub = _pad_to(ref_sub_local.astype(jnp.int32), rows, 0, -1).reshape(-1)
    start = _pad_to(cand_starts(cand_sub_local, max_subs), bc, 1,
                    cand_sub_local.shape[1])
    raw = stjoin_sim_fused_flat_cube(
        view(join.best_w, 0.0), view(join.best_idx, -1), rsub, start,
        _resolve_plan(tile_ids, rows, bc, bm), rows=rows, M=M, ms=max_subs,
        bc=bc, interpret=interpret)
    return raw[:T * max_subs, :C * max_subs]


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
                       **kw):
    """Kernel-backed Problem 1: ``(JoinResult, matches)``, the join with
    DTJ's delta_t refine applied in the kernel (``delta_t`` concrete; 0
    compiles the plain join, and ``matches`` is None).

    ``use_index=True`` routes through the grid-pruned kernel (requires
    concrete inputs for the host-side planning pass); output is identical.
    """
    join = best_match_join_pruned if use_index else best_match_join_kernel
    return join(ref, cand, eps_sp, eps_t, delta_t, **kw)
