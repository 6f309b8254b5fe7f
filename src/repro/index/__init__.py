"""Spatiotemporal candidate-pruning index for the subtrajectory join.

``grid`` — fixed-resolution (eps-derived) grid over tile bounding boxes:
grid fitting on the device, CSR cell tables, conservative candidate-tile
masks, compacted tile lists.
"""
from repro.index.grid import (
    CellTable,
    GridFit,
    GridSpec,
    PruneStats,
    TileBoxes,
    build_cell_table,
    candidate_tile_mask,
    cell_coords,
    coarse_pair_mask,
    compact_candidates,
    exact_pair_mask,
    fit_grid,
    fit_grid_arrays,
    plan_max_tiles,
    point_block_boxes,
    prune_stats,
    traj_block_boxes,
    trajectory_pair_mask,
)

__all__ = [
    "CellTable",
    "GridFit",
    "GridSpec",
    "PruneStats",
    "TileBoxes",
    "build_cell_table",
    "candidate_tile_mask",
    "cell_coords",
    "coarse_pair_mask",
    "compact_candidates",
    "exact_pair_mask",
    "fit_grid",
    "fit_grid_arrays",
    "plan_max_tiles",
    "point_block_boxes",
    "prune_stats",
    "traj_block_boxes",
    "trajectory_pair_mask",
]
