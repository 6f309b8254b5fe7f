"""The planners' compiled steps (``plan_join_index``, ``plan_fused_tiles``
in ``repro.kernels.stjoin.ops``): the same plans as the eager composition
of ``repro.index.grid``'s functions, every one equal to the plan of the
exact test alone, and one compile per shape whatever the data and eps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.types import TrajectoryBatch
from repro.index import grid as gridx
from repro.kernels.stjoin import ops

EPS_SP, EPS_T = 2.0, 10.0


def _batch(rng, T, M, *, invalid_rows=(), scale=10.0):
    """Tracks at spread start times and places, so the index prunes."""
    t0 = rng.uniform(0, 200, (T, 1))
    x = rng.uniform(0, scale, (T, 1)) + rng.uniform(0, 1, (T, M))
    y = rng.uniform(0, scale, (T, 1)) + rng.uniform(0, 1, (T, M))
    t = t0 + np.sort(rng.uniform(0, 20, (T, M)), axis=1)
    v = rng.uniform(0, 1, (T, M)) > 0.15
    v[list(invalid_rows)] = False
    return TrajectoryBatch(
        x=jnp.asarray(x, jnp.float32), y=jnp.asarray(y, jnp.float32),
        t=jnp.asarray(t, jnp.float32), valid=jnp.asarray(v),
        traj_id=jnp.arange(T, dtype=jnp.int32))


def _eager_plan(ref, cand, bp, bc, pad_c, use_cells):
    """The plan from ``grid.py``'s functions called one by one, operands
    padded with invalid points on the host.  Returns the plan's
    ``(mask, counts, tile_ids, K, kept)`` and the exact-only mask."""
    def flat(a, fill):
        a = np.asarray(a).reshape(-1)
        return jnp.asarray(np.concatenate(
            [a, np.full((-a.size) % bp, fill, a.dtype)]))

    def rows(a, fill):
        a = np.asarray(a)
        return jnp.asarray(np.concatenate(
            [a, np.full((pad_c, a.shape[1]), fill, a.dtype)]))

    rb = gridx.point_block_boxes(flat(ref.x, 0), flat(ref.y, 0),
                                 flat(ref.t, 0), flat(ref.valid, False), bp)
    cb = gridx.traj_block_boxes(rows(cand.x, 0), rows(cand.y, 0),
                                rows(cand.t, 0), rows(cand.valid, False), bc)
    exact = np.asarray(gridx.exact_pair_mask(rb, cb, EPS_SP, EPS_T))
    mask = exact
    if use_cells:
        spec = gridx.fit_grid(cb, EPS_SP, EPS_T)
        table = gridx.build_cell_table(spec, cb)
        mask = np.asarray(gridx.candidate_tile_mask(
            spec, table.coords, rb, cb, EPS_SP, EPS_T))
    counts = mask.sum(axis=1).astype(np.int32)
    K = gridx.plan_max_tiles(counts)
    ids, _ = gridx.compact_candidates(jnp.asarray(mask), K)
    return (mask, counts, np.asarray(ids), K, int(mask.sum())), exact


def _cases(case):
    rng = np.random.default_rng({"seed0": 0, "seed1": 1, "seed2": 2,
                                 "empty_blocks": 3, "all_invalid": 4}[case])
    if case == "all_invalid":
        return _batch(rng, 12, 16, invalid_rows=range(12)), \
            _batch(rng, 11, 16, invalid_rows=range(11))
    if case == "empty_blocks":
        # ref rows 2-3 are one whole block of 32 points; candidate rows
        # 4-7 one whole block of 4 rows (and two of 2)
        return _batch(rng, 12, 16, invalid_rows=(2, 3)), \
            _batch(rng, 11, 16, invalid_rows=(4, 5, 6, 7))
    return _batch(rng, 12, 16), _batch(rng, 11, 16)


CASES = ["seed0", "seed1", "seed2", "empty_blocks", "all_invalid"]


@pytest.mark.parametrize("use_cells", [True, False])
@pytest.mark.parametrize("case", CASES)
def test_compiled_plans_equal_the_eager_composition(case, use_cells):
    ref, cand = _cases(case)
    C = cand.x.shape[0]

    # the materializing join's planning: 32-point ref blocks, 2-row
    # candidate blocks (11 rows padded by 1)
    mask, counts, grid = ops.plan_join_index(ref, cand, EPS_SP, EPS_T,
                                             bp=32, bc=2, use_cells=use_cells)
    assert (grid is None) == (not use_cells)
    ids, _, K, kept = ops._compact_tiles(mask, counts, None)
    got = (np.asarray(mask), np.asarray(counts), np.asarray(ids), K, kept)
    want, exact = _eager_plan(ref, cand, 32, 2, (-C) % 2, use_cells)
    exact_plan, _ = _eager_plan(ref, cand, 32, 2, (-C) % 2, False)
    for g, w, e in zip(got, want, exact_plan):
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(g, e)
    np.testing.assert_array_equal(got[0], exact)

    # the fused sweep's planning: 2 rows of 16 points a ref block, 4-row
    # candidate blocks, candidates padded to 32 rows
    tp = ops.plan_fused_tiles(ref.x, ref.y, ref.t, ref.valid, cand.x, cand.y,
                              cand.t, cand.valid, EPS_SP, EPS_T, rows=2, bc=4,
                              bm=16, use_cells=use_cells)
    assert (tp.rows, tp.bc, tp.bm) == (2, 4, 16)
    want, exact = _eager_plan(ref, cand, 32, 4, (-C) % 32, use_cells)
    exact_plan, _ = _eager_plan(ref, cand, 32, 4, (-C) % 32, False)
    got_ids = np.asarray(tp.tile_ids)
    for w in (want, exact_plan):
        np.testing.assert_array_equal(got_ids, w[2])
        assert got_ids.shape[1] == w[3] and int((got_ids >= 0).sum()) == w[4]
    np.testing.assert_array_equal(want[0], exact)
    if case == "all_invalid":
        assert K == 1 and kept == 0 and got_ids.shape[1] == 1
        assert (got_ids == -1).all()
    else:
        assert 0 < kept < mask.size


PROGRAMS = ("_plan_boxes", "_plan_grid", "_plan_mask", "_plan_compact")


@pytest.mark.parametrize("planner", ["join", "sweep"])
def test_planning_compiles_once_per_shape(planner):
    """Two batches of one shape, with other data and other eps: each step
    program compiles for the first and nothing compiles for the second.
    A ``max_tiles`` below the need still raises."""
    T, M = (13, 10) if planner == "join" else (14, 12)
    rng = np.random.default_rng(21)
    batches = [_batch(rng, T, M) for _ in range(2)]

    def plan(b, eps_sp, eps_t, max_tiles):
        if planner == "join":
            mask, counts, _ = ops.plan_join_index(b, b, eps_sp, eps_t,
                                                  bp=20, bc=2)
            return ops._compact_tiles(mask, counts, max_tiles)[2]
        tp = ops.plan_fused_tiles(b.x, b.y, b.t, b.valid, b.x, b.y, b.t,
                                  b.valid, eps_sp, eps_t, rows=2, bc=4,
                                  bm=M, max_tiles=max_tiles)
        return tp.tile_ids.shape[1]

    n_cand = 7 if planner == "join" else 8      # candidate blocks
    programs = [getattr(ops, f) for f in PROGRAMS]
    for f in programs:
        f.clear_cache()
    size = lambda: [f._cache_size() for f in programs]
    assert plan(batches[0], EPS_SP, EPS_T, n_cand) == n_cand
    first = size()
    assert first == [1, 1, 1, 1]

    events = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        assert plan(batches[1], 1.5, 25.0, n_cand) == n_cand
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert size() == first
    assert events == []

    dense = _batch(rng, T, M, scale=0.5)        # every tile kept
    with pytest.raises(ValueError, match="max_tiles"):
        plan(dense, EPS_SP, 500.0, 1)
