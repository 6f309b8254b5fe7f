"""Spatiotemporal candidate-pruning index: the pruned join must be
*bit-identical* to the dense join — the index is a pure accelerator, never
an approximation.  Covers random batches (property test), edge cells
(points exactly on cell/eps boundaries), and all-invalid tiles."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.geometry import subtrajectory_join as geo_join
from repro.core.types import TrajectoryBatch
from repro.index import grid as gridx
from repro.kernels.stjoin.ops import (
    best_match_join_kernel,
    best_match_join_pruned,
)


def _batch(rng, T, M, *, invalid_rows=(), invalid_frac=0.15, scale=10.0):
    x = rng.uniform(0, scale, (T, M)).astype(np.float32)
    y = rng.uniform(0, scale, (T, M)).astype(np.float32)
    t = np.sort(rng.uniform(0, 50, (T, M)), axis=1).astype(np.float32)
    v = rng.uniform(0, 1, (T, M)) > invalid_frac
    for r in invalid_rows:
        v[r] = False
    return TrajectoryBatch(
        x=jnp.asarray(x), y=jnp.asarray(y), t=jnp.asarray(t),
        valid=jnp.asarray(v), traj_id=jnp.arange(T, dtype=jnp.int32))


def _assert_bitwise_equal(dense, pruned):
    assert np.array_equal(np.asarray(dense.best_w),
                          np.asarray(pruned.best_w))
    assert np.array_equal(np.asarray(dense.best_idx),
                          np.asarray(pruned.best_idx))


# ---------------------------- grid structure --------------------------------

def test_cell_table_is_partition_of_nonempty_tiles():
    rng = np.random.default_rng(0)
    b = _batch(rng, 8, 32)
    boxes = gridx.traj_block_boxes(b.x, b.y, b.t, b.valid, 2)
    spec = gridx.fit_grid(boxes, 2.0, 10.0)
    table = gridx.build_cell_table(spec, boxes)
    order = np.asarray(table.order)
    starts = np.asarray(table.starts)
    cell_of = np.asarray(table.cell_of)
    nonempty = np.asarray(boxes.nonempty)
    # order is a permutation of all tile ids
    assert sorted(order.tolist()) == list(range(boxes.num_tiles))
    # CSR covers exactly the nonempty tiles
    assert starts[-1] == nonempty.sum()
    for c in range(spec.num_cells):
        for tid in order[starts[c]:starts[c + 1]]:
            assert cell_of[tid] == c
    # empty tiles are parked past the end
    assert (cell_of[~nonempty] == spec.num_cells).all()


def test_fit_grid_cell_size_is_eps_derived():
    """Docstring contract: cells start at (eps_sp, eps_t) and only coarsen
    when an axis would exceed max_cells_per_axis."""
    rng = np.random.default_rng(1)
    b = _batch(rng, 4, 16, scale=5.0)
    boxes = gridx.traj_block_boxes(b.x, b.y, b.t, b.valid, 2)
    spec = gridx.fit_grid(boxes, 2.0, 10.0)
    assert spec.cell_sp >= 2.0 and spec.cell_t >= 10.0
    tiny = gridx.fit_grid(boxes, 0.001, 0.001, max_cells_per_axis=4)
    assert tiny.nx <= 4 and tiny.ny <= 4 and tiny.nt <= 4


def test_coarse_mask_is_superset_of_exact():
    rng = np.random.default_rng(2)
    ref = _batch(rng, 8, 32)
    cand = _batch(rng, 8, 32)
    rb = gridx.point_block_boxes(ref.x.reshape(-1), ref.y.reshape(-1),
                                 ref.t.reshape(-1), ref.valid.reshape(-1), 32)
    cb = gridx.traj_block_boxes(cand.x, cand.y, cand.t, cand.valid, 2)
    spec = gridx.fit_grid(cb, 2.0, 10.0)
    table = gridx.build_cell_table(spec, cb)
    coarse = np.asarray(gridx.coarse_pair_mask(spec, table.coords, rb, cb,
                                               2.0, 10.0))
    exact = np.asarray(gridx.exact_pair_mask(rb, cb, 2.0, 10.0))
    assert (coarse | ~exact).all()      # exact => coarse


# ------------------------- pruned == dense parity ---------------------------

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_pruned_join_matches_dense(seed):
    rng = np.random.default_rng(seed)
    ref = _batch(rng, 8, 32)
    cand = _batch(rng, 8, 32)
    dense = best_match_join_kernel(ref, cand, 2.0, 10.0, bp=32, bc=2, bm=16)
    pruned = best_match_join_pruned(ref, cand, 2.0, 10.0, bp=32, bc=2, bm=16)
    _assert_bitwise_equal(dense, pruned)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_pruned_join_matches_dense_without_cells(seed):
    """Exact-bbox-only planning path (use_cells=False)."""
    rng = np.random.default_rng(seed)
    ref = _batch(rng, 4, 16)
    cand = _batch(rng, 8, 16)
    dense = best_match_join_kernel(ref, cand, 3.0, 20.0, bp=16, bc=2, bm=16)
    pruned = best_match_join_pruned(ref, cand, 3.0, 20.0, bp=16, bc=2, bm=16,
                                    use_cells=False)
    _assert_bitwise_equal(dense, pruned)


def test_pruned_join_edge_cells():
    """Points exactly at eps distance and on cell boundaries must be kept:
    the bbox test uses <=, mirroring the join's cylinder predicate."""
    T, M = 2, 16
    x = np.zeros((T, M), np.float32)
    y = np.zeros((T, M), np.float32)
    t = np.tile(np.arange(M, dtype=np.float32), (T, 1))
    # row 1 sits exactly eps_sp away from row 0 in x
    x[1] = 2.0
    b = TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y), t=jnp.asarray(t),
                        valid=jnp.ones((T, M), bool),
                        traj_id=jnp.arange(T, dtype=jnp.int32))
    dense = best_match_join_kernel(b, b, 2.0, 1.0, bp=16, bc=1, bm=16)
    pruned = best_match_join_pruned(b, b, 2.0, 1.0, bp=16, bc=1, bm=16)
    _assert_bitwise_equal(dense, pruned)
    # the eps-boundary pair really matches (w == 1 - eps/eps == 0 is culled;
    # nudge inside to see a positive weight)
    x[1] = 1.999
    b2 = b.replace(x=jnp.asarray(x))
    dense2 = best_match_join_kernel(b2, b2, 2.0, 1.0, bp=16, bc=1, bm=16)
    pruned2 = best_match_join_pruned(b2, b2, 2.0, 1.0, bp=16, bc=1, bm=16)
    _assert_bitwise_equal(dense2, pruned2)
    assert float(np.asarray(pruned2.best_w).max()) > 0.0


def test_pruned_join_all_invalid_tiles():
    rng = np.random.default_rng(7)
    ref = _batch(rng, 8, 16, invalid_rows=(1, 2, 5))
    cand = _batch(rng, 8, 16, invalid_rows=(0, 3))
    dense = best_match_join_kernel(ref, cand, 2.0, 10.0, bp=16, bc=2, bm=16)
    pruned = best_match_join_pruned(ref, cand, 2.0, 10.0, bp=16, bc=2, bm=16)
    _assert_bitwise_equal(dense, pruned)


def test_pruned_join_everything_invalid():
    rng = np.random.default_rng(8)
    ref = _batch(rng, 4, 16, invalid_rows=range(4))
    cand = _batch(rng, 4, 16, invalid_rows=range(4))
    dense = best_match_join_kernel(ref, cand, 2.0, 10.0, bp=16, bc=2, bm=16)
    pruned, stats = best_match_join_pruned(
        ref, cand, 2.0, 10.0, bp=16, bc=2, bm=16, return_stats=True)
    _assert_bitwise_equal(dense, pruned)
    assert int(stats.kept_tiles) == 0
    assert (np.asarray(pruned.best_w) == 0).all()
    assert (np.asarray(pruned.best_idx) == -1).all()


def test_pruned_join_prunes_separated_clusters():
    """Two well-separated clusters: cross-cluster tiles must be pruned and
    the surviving-tile count strictly below dense."""
    rng = np.random.default_rng(9)
    near = _batch(rng, 4, 16, scale=1.0)
    far = _batch(rng, 4, 16, scale=1.0)
    batch = TrajectoryBatch(
        x=jnp.concatenate([near.x, far.x + 100.0]),
        y=jnp.concatenate([near.y, far.y + 100.0]),
        t=jnp.concatenate([near.t, far.t]),
        valid=jnp.concatenate([near.valid, far.valid]),
        traj_id=jnp.arange(8, dtype=jnp.int32))
    dense = best_match_join_kernel(batch, batch, 2.0, 10.0, bp=16, bc=2, bm=16)
    pruned, stats = best_match_join_pruned(
        batch, batch, 2.0, 10.0, bp=16, bc=2, bm=16, return_stats=True)
    _assert_bitwise_equal(dense, pruned)
    assert int(stats.kept_tiles) < stats.dense_tiles
    assert int(stats.kept_tiles) > 0


def test_max_tiles_too_small_raises():
    rng = np.random.default_rng(10)
    b = _batch(rng, 8, 16, scale=0.5)      # everything close -> no pruning
    with pytest.raises(ValueError, match="max_tiles"):
        best_match_join_pruned(b, b, 2.0, 50.0, bp=16, bc=2, bm=16,
                               max_tiles=1)


# --------------------- reference-path & API integration ---------------------

def test_geometry_join_use_index_is_lossless():
    rng = np.random.default_rng(11)
    ref = _batch(rng, 6, 24)
    cand = _batch(rng, 6, 24)
    base = geo_join(ref, cand, 2.0, 10.0)
    idx = geo_join(ref, cand, 2.0, 10.0, use_index=True)
    _assert_bitwise_equal(base, idx)


def test_kernel_subtrajectory_join_use_index():
    from repro.kernels.stjoin.ops import subtrajectory_join as k_join
    rng = np.random.default_rng(12)
    ref = _batch(rng, 4, 32)
    cand = _batch(rng, 4, 32)
    base = k_join(ref, cand, 2.0, 10.0, delta_t=3.0, bp=32, bc=2, bm=16)
    idx = k_join(ref, cand, 2.0, 10.0, delta_t=3.0, use_index=True,
                 bp=32, bc=2, bm=16)
    _assert_bitwise_equal(base, idx)
