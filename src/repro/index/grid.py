"""Fixed-resolution spatiotemporal grid index for candidate-tile pruning.

The DTJ join is the dominant cost of the whole DSC pipeline; the paper (and
its companion "Distributed Subtrajectory Join on Massive Datasets") gets its
scalability from discarding candidate pairs *before* the expensive refine
step.  This module is that filter, recast for fixed-shape JAX: instead of a
dynamic R-tree over individual points we index *tiles* — the same ``[bp]``
reference-point blocks and ``[bc, Mc]`` candidate-trajectory blocks the
Pallas ``stjoin`` kernel iterates — and emit, per reference block, the
compacted list of candidate tiles that can possibly contain a match.

Cell-size contract (eps-derived)
--------------------------------
A match requires ``d_sp <= eps_sp`` and ``|dt| <= eps_t``, so the natural
grid resolution is the matching threshold itself: cells are
``eps_sp x eps_sp x eps_t`` (spatial x, spatial y, time), clamped so no
axis exceeds ``max_cells_per_axis`` (coarser cells on huge domains — the
index gets less selective, never incorrect).  With cells >= the matching
radius, every point within ``eps`` of a cell lies in that cell's 3^3
neighborhood, which is what makes the coarse cell test below conservative.

Pruning is two-staged and *conservative by construction*:

1. coarse — each candidate tile is placed in the grid cell of its bbox
   center (``cell_coords``); a reference tile keeps the cells overlapping
   its bbox expanded by ``eps + max tile half-extent`` per axis.
   ``build_cell_table`` also buckets the tiles into CSR-style
   ``order``/``starts`` lists for consumers that gather per cell.
2. exact  — surviving tiles are re-checked with the eps-expanded
   bounding-box distance test (Euclidean in space, interval in time), so a
   kept tile really can contain a matching point pair and a dropped tile
   provably cannot.

Because stage 2 never drops a tile that could match, the pruned join is
*bit-identical* to the dense join (``tests/test_index.py`` enforces this),
while the surviving-tile count — the quantity ``benchmarks/kernel_bench.py``
records — shrinks with data clustering exactly as the paper's Fig. 8 run
does.

Every function here is traceable except ``fit_grid`` and
``build_cell_table``, which need the grid's cell counts static.  The
planners (``repro.kernels.stjoin.ops.plan_join_index`` and
``plan_fused_tiles``) therefore fit the grid on the device
(``fit_grid_arrays``, traced origin, cell sizes and cell counts) and run
each step as one compiled program: block boxes, grid and center cells,
mask and counts, compaction at the static width ``K``.  The one host copy
of a planning is its survivor counts, for ``K``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.utils.tree import pytree_dataclass


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static grid geometry: origin, cell sizes, cell counts per axis.

    Static (hashable) so it can close over ``jit``-compiled functions; the
    data-dependent parts (tile bboxes, CSR tables) are traced arrays.
    """

    x0: float
    y0: float
    t0: float
    cell_sp: float       # spatial cell edge (x and y), >= eps_sp
    cell_t: float        # temporal cell extent, >= eps_t
    nx: int
    ny: int
    nt: int

    @property
    def num_cells(self) -> int:
        return self.nx * self.ny * self.nt


@pytree_dataclass
class TileBoxes:
    """Per-tile axis-aligned bounding boxes over the *valid* points only."""

    xmin: jnp.ndarray    # [n] float32 (+inf for empty tiles)
    xmax: jnp.ndarray    # [n] float32 (-inf for empty tiles)
    ymin: jnp.ndarray
    ymax: jnp.ndarray
    tmin: jnp.ndarray
    tmax: jnp.ndarray
    nonempty: jnp.ndarray  # [n] bool — tile holds >= 1 valid point

    @property
    def num_tiles(self) -> int:
        return self.xmin.shape[0]


@pytree_dataclass
class CellTable:
    """CSR-style cell -> tile-id lists (tiles sorted by their center cell).

    ``order[starts[c]:starts[c+1]]`` are the tile ids whose bbox center
    falls in cell ``c``; empty tiles are parked past ``starts[-1]``.
    """

    order: jnp.ndarray    # [n] int32 tile ids, cell-sorted
    starts: jnp.ndarray   # [num_cells + 1] int32 CSR offsets
    cell_of: jnp.ndarray  # [n] int32 center cell id (num_cells for empties)
    coords: jnp.ndarray   # [n, 3] int32 (ix, iy, it) center cell coords


@pytree_dataclass
class PruneStats:
    """What the index did: dense vs surviving candidate-tile counts."""

    kept_tiles: jnp.ndarray    # [] int32 — sum over ref tiles of survivors
    dense_tiles: int           # static: n_ref_tiles * n_cand_tiles
    max_per_ref: jnp.ndarray   # [] int32 — worst-case survivors per ref tile


# --------------------------------------------------------------------------
# bbox construction
# --------------------------------------------------------------------------

def _masked_boxes(x, y, t, valid):
    """Min/max over the last axis with invalid slots neutralized."""
    inf = jnp.float32(jnp.inf)
    lo = lambda a: jnp.min(jnp.where(valid, a, inf), axis=-1)
    hi = lambda a: jnp.max(jnp.where(valid, a, -inf), axis=-1)
    return TileBoxes(
        xmin=lo(x), xmax=hi(x), ymin=lo(y), ymax=hi(y),
        tmin=lo(t), tmax=hi(t), nonempty=jnp.any(valid, axis=-1))


def point_block_boxes(x, y, t, valid, block: int) -> TileBoxes:
    """Bboxes of consecutive ``block``-point groups of flattened arrays.

    Inputs are ``[P]`` with ``P % block == 0`` (the stjoin kernel's padded
    reference layout); output tiles align with the kernel's ``i`` grid axis.
    """
    P = x.shape[0]
    assert P % block == 0, (P, block)
    n = P // block
    rs = lambda a: a.reshape(n, block)
    return _masked_boxes(rs(x), rs(y), rs(t), rs(valid))


def traj_block_boxes(x, y, t, valid, block: int) -> TileBoxes:
    """Bboxes of ``block`` consecutive trajectory rows (all their points).

    Inputs are ``[C, Mc]`` with ``C % block == 0``; output tiles align with
    the kernel's candidate ``j`` grid axis.
    """
    C, Mc = x.shape
    assert C % block == 0, (C, block)
    n = C // block
    rs = lambda a: a.reshape(n, block * Mc)
    return _masked_boxes(rs(x), rs(y), rs(t), rs(valid))


# --------------------------------------------------------------------------
# grid fitting + CSR cell table
# --------------------------------------------------------------------------

@pytree_dataclass
class GridFit:
    """A ``GridSpec``'s fields as device scalars: the grid as fitted under
    ``jit`` by ``fit_grid_arrays``.  ``cell_coords`` and
    ``coarse_pair_mask`` take it in place of a ``GridSpec``."""

    x0: jnp.ndarray      # [] float32
    y0: jnp.ndarray
    t0: jnp.ndarray
    cell_sp: jnp.ndarray
    cell_t: jnp.ndarray
    nx: jnp.ndarray      # [] int32, in [1, max_cells_per_axis]
    ny: jnp.ndarray
    nt: jnp.ndarray


def fit_grid_arrays(boxes: TileBoxes, eps_sp, eps_t, *,
                    max_cells_per_axis: int = 64) -> GridFit:
    """Fit the grid to the nonempty tiles' bboxes; traceable, so a planner
    fits it inside its compiled program with ``eps_sp``/``eps_t`` traced.

    Cell sizes start at the matching thresholds (``eps_sp``, ``eps_t``) and
    are coarsened only when the domain would need more than
    ``max_cells_per_axis`` cells on some axis.  Empty inputs yield a 1-cell
    grid at the origin.
    """
    ne = boxes.nonempty
    some = jnp.any(ne)
    max_n = jnp.float32(max_cells_per_axis)

    def axis(lo_a, hi_a, base):
        lo = jnp.where(some, jnp.min(jnp.where(ne, lo_a, jnp.inf)), 0.0)
        hi = jnp.where(some, jnp.max(jnp.where(ne, hi_a, -jnp.inf)), 0.0)
        cell = jnp.maximum(jnp.asarray(base, jnp.float32), 1e-6)
        extent = jnp.maximum(hi - lo, 0.0)
        coarsen = jnp.floor(extent / cell) + 1 > max_n
        cell = jnp.where(coarsen, extent / max_n * (1 + 1e-6), cell)
        n = jnp.minimum(jnp.floor(extent / cell) + 1, max_n)
        return lo, cell, n.astype(jnp.int32)

    x0, csx, nx = axis(boxes.xmin, boxes.xmax, eps_sp)
    y0, csy, ny = axis(boxes.ymin, boxes.ymax, eps_sp)
    t0, cst, nt = axis(boxes.tmin, boxes.tmax, eps_t)
    # one spatial resolution for both axes (square cells)
    return GridFit(x0=x0, y0=y0, t0=t0, cell_sp=jnp.maximum(csx, csy),
                   cell_t=cst, nx=nx, ny=ny, nt=nt)


@functools.partial(jax.jit, static_argnames=("max_cells_per_axis",))
def _fit_grid_packed(boxes, eps_sp, eps_t, *, max_cells_per_axis):
    fit = fit_grid_arrays(boxes, eps_sp, eps_t,
                          max_cells_per_axis=max_cells_per_axis)
    return jnp.stack([v.astype(jnp.float32) for v in jax.tree.leaves(fit)])


def fit_grid(boxes: TileBoxes, eps_sp: float, eps_t: float, *,
             max_cells_per_axis: int = 64) -> GridSpec:
    """Static ``GridSpec`` of the grid ``fit_grid_arrays`` fits, for
    consumers that need static cell counts (``build_cell_table``).

    The fit runs compiled on the device; its eight numbers come to the
    host in one copy (the cell counts are at most ``max_cells_per_axis``,
    exact in float32).
    """
    x0, y0, t0, cell_sp, cell_t, nx, ny, nt = np.asarray(_fit_grid_packed(
        boxes, eps_sp, eps_t, max_cells_per_axis=max_cells_per_axis)).tolist()
    return GridSpec(x0=x0, y0=y0, t0=t0, cell_sp=cell_sp, cell_t=cell_t,
                    nx=int(nx), ny=int(ny), nt=int(nt))


def _center_coords(spec, boxes: TileBoxes):
    """Integer cell coords of each tile's bbox center, clipped into range."""
    def quant(lo, hi, origin, cell, n):
        center = 0.5 * (lo + hi)
        ix = jnp.floor((center - origin) / cell).astype(jnp.int32)
        return jnp.clip(ix, 0, n - 1)

    ix = quant(boxes.xmin, boxes.xmax, spec.x0, spec.cell_sp, spec.nx)
    iy = quant(boxes.ymin, boxes.ymax, spec.y0, spec.cell_sp, spec.ny)
    it = quant(boxes.tmin, boxes.tmax, spec.t0, spec.cell_t, spec.nt)
    return ix, iy, it


def cell_coords(spec, boxes: TileBoxes) -> jnp.ndarray:
    """[n, 3] int32 ``(ix, iy, it)`` cell of each tile's bbox center, -1
    for empty tiles.  ``spec`` is a ``GridSpec`` or a traced ``GridFit``."""
    coords = jnp.stack(_center_coords(spec, boxes), axis=-1)
    return jnp.where(boxes.nonempty[:, None], coords, -1)


def build_cell_table(spec: GridSpec, boxes: TileBoxes) -> CellTable:
    """Bucket tiles into grid cells; CSR arrays built under ``jit``.

    The pruning queries below consume only ``coords`` (vectorized cell
    range tests), and the planners compute those alone (``cell_coords``);
    the ``order``/``starts`` CSR lists exist for consumers that gather
    per-cell tile lists directly — the planned segmentation neighbor
    masks and similarity scatter (ROADMAP).
    """
    coords = cell_coords(spec, boxes)
    ix, iy, it = coords[:, 0], coords[:, 1], coords[:, 2]
    cell = (ix * spec.ny + iy) * spec.nt + it
    cell = jnp.where(boxes.nonempty, cell, spec.num_cells)  # park empties
    order = jnp.argsort(cell, stable=True).astype(jnp.int32)
    sorted_cells = cell[order]
    starts = jnp.searchsorted(
        sorted_cells, jnp.arange(spec.num_cells + 1)).astype(jnp.int32)
    return CellTable(order=order, starts=starts,
                     cell_of=cell.astype(jnp.int32), coords=coords)


# --------------------------------------------------------------------------
# candidate queries
# --------------------------------------------------------------------------

def _axis_gap(alo, ahi, blo, bhi):
    """Separation between intervals [alo, ahi] and [blo, bhi] (0 = overlap).

    Empty boxes carry +/-inf bounds; ``maximum(..., 0)`` of inf gaps keeps
    them infinite, so empty tiles never pair with anything.
    """
    return jnp.maximum(jnp.maximum(blo - ahi, alo - bhi), 0.0)


def exact_pair_mask(ref: TileBoxes, cand: TileBoxes, eps_sp, eps_t):
    """[nR, nC] bool: candidate tile can contain a match for the ref tile.

    Euclidean bbox-distance test in space, interval-gap test in time —
    exactly the cylinder predicate of the join lifted to bounding boxes, so
    the mask is conservative: ``False`` proves no point pair can match.
    """
    gx = _axis_gap(ref.xmin[:, None], ref.xmax[:, None],
                   cand.xmin[None, :], cand.xmax[None, :])
    gy = _axis_gap(ref.ymin[:, None], ref.ymax[:, None],
                   cand.ymin[None, :], cand.ymax[None, :])
    gt = _axis_gap(ref.tmin[:, None], ref.tmax[:, None],
                   cand.tmin[None, :], cand.tmax[None, :])
    eps_sp = jnp.float32(eps_sp)
    sp_ok = gx * gx + gy * gy <= eps_sp * eps_sp
    ok = sp_ok & (gt <= jnp.float32(eps_t))
    return ok & ref.nonempty[:, None] & cand.nonempty[None, :]


def coarse_pair_mask(spec, coords, ref: TileBoxes, cand: TileBoxes,
                     eps_sp, eps_t):
    """[nR, nC] bool coarse cell test (conservative superset of exact).

    A candidate tile is kept when its *center cell* (``coords``, from
    ``cell_coords``) lies inside the ref tile's bbox expanded by ``eps``
    plus the fleet-wide max candidate tile half-extent — the slack that
    makes center-bucketing safe for tiles that straddle cell boundaries.
    Cell quantization is monotone, so the test stays conservative for any
    origin and cell size: ``spec`` may be a ``GridSpec`` or a ``GridFit``.
    """
    ext = lambda lo, hi: jnp.where(cand.nonempty, hi - lo, 0.0)
    half_x = 0.5 * jnp.max(ext(cand.xmin, cand.xmax), initial=0.0)
    half_y = 0.5 * jnp.max(ext(cand.ymin, cand.ymax), initial=0.0)
    half_t = 0.5 * jnp.max(ext(cand.tmin, cand.tmax), initial=0.0)

    def rng(lo, hi, pad, origin, cell, n):
        lo_i = jnp.floor((lo - pad - origin) / cell).astype(jnp.int32)
        hi_i = jnp.floor((hi + pad - origin) / cell).astype(jnp.int32)
        return jnp.clip(lo_i, 0, n - 1), jnp.clip(hi_i, 0, n - 1)

    eps_sp = jnp.float32(eps_sp)
    eps_t = jnp.float32(eps_t)
    xlo, xhi = rng(ref.xmin, ref.xmax, eps_sp + half_x,
                   spec.x0, spec.cell_sp, spec.nx)
    ylo, yhi = rng(ref.ymin, ref.ymax, eps_sp + half_y,
                   spec.y0, spec.cell_sp, spec.ny)
    tlo, thi = rng(ref.tmin, ref.tmax, eps_t + half_t,
                   spec.t0, spec.cell_t, spec.nt)

    cc = coords                                    # [nC, 3]
    inx = (cc[None, :, 0] >= xlo[:, None]) & (cc[None, :, 0] <= xhi[:, None])
    iny = (cc[None, :, 1] >= ylo[:, None]) & (cc[None, :, 1] <= yhi[:, None])
    int_ = (cc[None, :, 2] >= tlo[:, None]) & (cc[None, :, 2] <= thi[:, None])
    return inx & iny & int_ & ref.nonempty[:, None] & cand.nonempty[None, :]


def candidate_tile_mask(spec, coords, ref: TileBoxes, cand: TileBoxes,
                        eps_sp, eps_t):
    """Coarse cell test refined by the exact eps-expanded bbox test."""
    coarse = coarse_pair_mask(spec, coords, ref, cand, eps_sp, eps_t)
    return coarse & exact_pair_mask(ref, cand, eps_sp, eps_t)


def compact_candidates(mask: jnp.ndarray, max_tiles: int):
    """[nR, nC] bool -> (tile_ids [nR, max_tiles] int32 -1-padded, counts).

    Surviving tile ids are emitted in ascending order (the dense kernel's
    iteration order, which keeps argmax tie-breaking bit-identical).  Ids
    beyond ``max_tiles`` are dropped — callers that need exactness must
    size ``max_tiles >= counts.max()`` (see ``plan_max_tiles``).
    """
    nR, nC = mask.shape
    idx = jnp.arange(nC, dtype=jnp.int32)
    key = jnp.where(mask, idx, nC + idx)          # survivors first, in order
    order = jnp.argsort(key, axis=1)[:, :max_tiles].astype(jnp.int32)
    counts = jnp.sum(mask, axis=1).astype(jnp.int32)
    slot = jnp.arange(max_tiles, dtype=jnp.int32)[None, :]
    tile_ids = jnp.where(slot < counts[:, None], order, -1)
    return tile_ids, counts


def plan_max_tiles(counts, *, multiple_of: int = 1) -> int:
    """Host-side: smallest static K (>= 1) covering every ref tile's list."""
    k = int(np.max(np.asarray(counts), initial=0))
    k = max(k, 1)
    return -(-k // multiple_of) * multiple_of


def prune_stats(counts, n_cand_tiles: int) -> PruneStats:
    n_ref = counts.shape[0]
    return PruneStats(
        kept_tiles=jnp.sum(counts).astype(jnp.int32),
        dense_tiles=int(n_ref * n_cand_tiles),
        max_per_ref=jnp.max(counts, initial=0).astype(jnp.int32))


# --------------------------------------------------------------------------
# row-level (per-trajectory) masks for the pure-jnp reference path
# --------------------------------------------------------------------------

def trajectory_pair_mask(ref_x, ref_y, ref_t, ref_valid,
                         cand_x, cand_y, cand_t, cand_valid,
                         eps_sp, eps_t):
    """[T, C] bool: candidate row can match some point of ref row.

    Row-granularity version of ``exact_pair_mask`` for the dense jnp
    reference join (``repro.core.geometry``) and the shard_map JOIN phase,
    where tiles are whole trajectory rows.  (The distributed halo filter
    in ``repro.core.distributed`` applies the same eps-expanded-bbox test
    per partition, pre-exchange, using the exchanged 6-float bboxes.)
    """
    rb = _masked_boxes(ref_x, ref_y, ref_t, ref_valid)
    cb = _masked_boxes(cand_x, cand_y, cand_t, cand_valid)
    return exact_pair_mask(rb, cb, eps_sp, eps_t)
