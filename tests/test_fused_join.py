"""Fused streaming join (epilogue fusion) vs the materializing oracle.

The fused kernels never build the [T, M, C] JoinResult cube; these tests pin
their three accumulators — vote sums (Eq. 4), bit-packed neighbor words
(Alg. 3 input), and the raw similarity scatter (Eq. 2) — against the
materializing reference path, including delta_t refinement, all-padding
rows, and shapes that leave ragged last tiles after padding.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import geometry, segmentation, similarity, voting
from repro.core.dsc import run_dsc
from repro.core.types import DSCParams, TrajectoryBatch
from repro.kernels.stjoin import ops as stjoin_ops


def _rand_batch(rng, T, M, pad_row=None):
    x = rng.uniform(0, 10, (T, M)).astype(np.float32)
    y = rng.uniform(0, 10, (T, M)).astype(np.float32)
    t = np.sort(rng.uniform(0, 50, (T, M)), axis=1).astype(np.float32)
    v = rng.uniform(0, 1, (T, M)) > 0.15
    ids = np.arange(T, dtype=np.int32)
    if pad_row is not None:
        v[pad_row] = False
        ids[pad_row] = -1
    return TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y),
                           t=jnp.asarray(t), valid=jnp.asarray(v),
                           traj_id=jnp.asarray(ids))


def _reference(ref, cand, eps_sp, eps_t, delta_t):
    join = geometry.subtrajectory_join(ref, cand, eps_sp, eps_t, delta_t)
    return (join, voting.point_voting(join),
            voting.neighbor_mask_packed(join))


def _reference_raw_sim(join, ref_seg, cand_seg, max_subs):
    """Un-normalized SP scatter straight from the cube (cross-join form)."""
    T, M, C = join.best_w.shape
    Mc = cand_seg.sub_local.shape[1]
    n_src, n_dst = T * max_subs, C * max_subs
    src = jnp.where(ref_seg.sub_local >= 0,
                    jnp.arange(T)[:, None] * max_subs + ref_seg.sub_local,
                    n_src)
    src = jnp.broadcast_to(src[:, :, None], (T, M, C))
    idx = jnp.clip(join.best_idx, 0, Mc - 1)
    csub = cand_seg.sub_local[jnp.arange(C)[None, None, :], idx]
    dst = jnp.where((join.best_idx >= 0) & (csub >= 0),
                    jnp.arange(C)[None, None, :] * max_subs + csub, n_dst)
    raw = jnp.zeros((n_src + 1, n_dst + 1), jnp.float32)
    raw = raw.at[src.reshape(-1), dst.reshape(-1)].add(
        join.best_w.reshape(-1))
    return raw[:n_src, :n_dst]


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([0.0, 4.0, 20.0]))
@settings(max_examples=8, deadline=None)
def test_fused_vote_and_masks_match_reference(seed, delta_t):
    rng = np.random.default_rng(seed)
    b = _rand_batch(rng, 5, 20, pad_row=int(seed) % 5)
    join, want_vote, want_words = _reference(b, b, 2.5, 12.0, delta_t)
    vote, words = stjoin_ops.stjoin_vote_fused(
        b, b, 2.5, 12.0, delta_t, rows=2, bc=2, bm=8)
    np.testing.assert_allclose(np.asarray(vote), np.asarray(want_vote),
                               atol=1e-5)
    assert (np.asarray(words) == np.asarray(want_words)).all()


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=6, deadline=None)
def test_fused_pruned_matches_dense_fused(seed):
    """The index-pruned fused sweep is lossless (conservative pruning)."""
    rng = np.random.default_rng(seed)
    b = _rand_batch(rng, 6, 16)
    _, want_vote, want_words = _reference(b, b, 2.0, 10.0, 3.0)
    tiles = stjoin_ops.plan_fused_tiles(
        b.x, b.y, b.t, b.valid, b.x, b.y, b.t, b.valid, 2.0, 10.0,
        rows=2, bc=2, bm=8)
    vote, words = stjoin_ops.stjoin_vote_fused_arrays(
        b.x, b.y, b.t, b.valid, b.traj_id,
        b.x, b.y, b.t, b.valid, b.traj_id,
        2.0, 10.0, 3.0, rows=2, bc=2, bm=8, tile_ids=tiles)
    np.testing.assert_allclose(np.asarray(vote), np.asarray(want_vote),
                               atol=1e-5)
    assert (np.asarray(words) == np.asarray(want_words)).all()


@pytest.mark.parametrize("T,M,C,Mc,rows,bc,bm,delta_t", [
    (5, 17, 7, 13, 3, 8, 8, 0.0),      # everything ragged
    (5, 17, 7, 13, 3, 8, 8, 7.0),
    (3, 40, 35, 11, 2, 32, 128, 0.0),  # bc == word width; bm > Mc
    (4, 8, 4, 8, 8, 4, 4, 7.0),        # rows > T (whole batch one block)
])
def test_fused_sim_matches_reference_cross_join(T, M, C, Mc, rows, bc, bm,
                                                delta_t):
    """Pass 2 against the cube scatter, with independent candidate-side
    segmentation (the cross-join form the distributed pipeline uses)."""
    rng = np.random.default_rng(T * 1000 + C)
    b = _rand_batch(rng, T, M, pad_row=0)
    c = _rand_batch(rng, C, Mc)
    max_subs = 4
    join, vote, _ = _reference(b, c, 2.5, 12.0, delta_t)
    cjoin, cvote, _ = _reference(c, c, 2.5, 12.0, delta_t)
    seg = segmentation.tsa1(
        voting.normalized_voting(vote, b.valid), b.valid, 3, 0.1, max_subs)
    cseg = segmentation.tsa1(
        voting.normalized_voting(cvote, c.valid), c.valid, 3, 0.1, max_subs)
    want = _reference_raw_sim(join, seg, cseg, max_subs)
    raw = stjoin_ops.stjoin_sim_fused(
        b, c, seg.sub_local, cseg.sub_local, max_subs, 2.5, 12.0, delta_t,
        rows=rows, bc=bc, bm=bm)
    np.testing.assert_allclose(np.asarray(raw), np.asarray(want), atol=1e-5)

    tiles = stjoin_ops.plan_fused_tiles(
        b.x, b.y, b.t, b.valid, c.x, c.y, c.t, c.valid, 2.5, 12.0,
        rows=rows, bc=bc, bm=bm)
    raw_p = stjoin_ops.stjoin_sim_fused(
        b, c, seg.sub_local, cseg.sub_local, max_subs, 2.5, 12.0, delta_t,
        tile_ids=tiles, rows=rows, bc=bc, bm=bm)
    np.testing.assert_allclose(np.asarray(raw_p), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("kw", [
    dict(mode="fused"),
    dict(mode="fused", use_index=True),
])
def test_run_dsc_fused_matches_materializing(fig1, fig1_params, kw):
    """Acceptance: identical clustering output, sim allclose, no join cube."""
    batch, _ = fig1
    a = run_dsc(batch, fig1_params)
    b = run_dsc(batch, fig1_params, **kw)
    assert b.join is None
    assert (np.asarray(a.result.member_of)
            == np.asarray(b.result.member_of)).all()
    assert (np.asarray(a.result.is_rep) == np.asarray(b.result.is_rep)).all()
    assert (np.asarray(a.result.is_outlier)
            == np.asarray(b.result.is_outlier)).all()
    np.testing.assert_allclose(np.asarray(a.sim), np.asarray(b.sim),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(a.vote), np.asarray(b.vote),
                               atol=1e-4)


def test_run_dsc_fused_tsa1_delta_t(fig1):
    """Fused mode with TSA1 segmentation and an active delta_t refine."""
    batch, _ = fig1
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=0.3, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa1")
    a = run_dsc(batch, params)
    b = run_dsc(batch, params, mode="fused")
    assert (np.asarray(a.result.member_of)
            == np.asarray(b.result.member_of)).all()
    np.testing.assert_allclose(np.asarray(a.sim), np.asarray(b.sim),
                               atol=1e-5)


def test_fused_vote_only_skips_masks():
    """with_masks=False (the TSA1 path) returns (vote, None) — identical
    votes, no packed-word accumulator built at all."""
    rng = np.random.default_rng(11)
    b = _rand_batch(rng, 5, 20)
    want_vote, _ = stjoin_ops.stjoin_vote_fused(
        b, b, 2.5, 12.0, 3.0, rows=2, bc=2, bm=8)
    vote, words = stjoin_ops.stjoin_vote_fused(
        b, b, 2.5, 12.0, 3.0, rows=2, bc=2, bm=8, with_masks=False)
    assert words is None
    np.testing.assert_allclose(np.asarray(vote), np.asarray(want_vote),
                               atol=1e-6)
    tiles = stjoin_ops.plan_fused_tiles(
        b.x, b.y, b.t, b.valid, b.x, b.y, b.t, b.valid, 2.5, 12.0,
        rows=2, bc=2, bm=8)
    vote_p, words_p = stjoin_ops.stjoin_vote_fused_arrays(
        b.x, b.y, b.t, b.valid, b.traj_id,
        b.x, b.y, b.t, b.valid, b.traj_id,
        2.5, 12.0, 3.0, rows=2, bc=2, bm=8, tile_ids=tiles,
        with_masks=False)
    assert words_p is None
    np.testing.assert_allclose(np.asarray(vote_p), np.asarray(want_vote),
                               atol=1e-6)


def test_fused_tile_plan_geometry_mismatch_rejected():
    """A plan reused under a different tile geometry would mis-address
    candidate blocks; the sweep must reject it instead of silently
    dropping candidates."""
    rng = np.random.default_rng(13)
    b = _rand_batch(rng, 6, 16)
    plan = stjoin_ops.plan_fused_tiles(
        b.x, b.y, b.t, b.valid, b.x, b.y, b.t, b.valid, 2.0, 10.0,
        rows=2, bc=2, bm=8)
    with pytest.raises(ValueError, match="geometry"):
        stjoin_ops.stjoin_vote_fused_arrays(
            b.x, b.y, b.t, b.valid, b.traj_id,
            b.x, b.y, b.t, b.valid, b.traj_id,
            2.0, 10.0, 0.0, rows=2, bc=4, bm=8, tile_ids=plan)


def test_fused_all_invalid_batch():
    """Degenerate input: no valid points anywhere -> zero accumulators."""
    T, M = 3, 12
    z = jnp.zeros((T, M), jnp.float32)
    b = TrajectoryBatch(x=z, y=z, t=z, valid=jnp.zeros((T, M), bool),
                        traj_id=jnp.full((T,), -1, jnp.int32))
    vote, words = stjoin_ops.stjoin_vote_fused(b, b, 1.0, 1.0, 0.0,
                                               rows=2, bc=2, bm=4)
    assert (np.asarray(vote) == 0).all()
    assert (np.asarray(words) == 0).all()


# ---------------------------------------------------------------------------
# The materializing join with DTJ's delta_t refine in the kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_index", [False, True])
@pytest.mark.parametrize("T,M,bp,bc,bm,delta_t", [
    (5, 24, 256, 2, 8, 6.0),    # 24 does not divide 256: 10 rows, cut to T
    (6, 16, 32, 2, 16, 4.0),    # two rows a block
    (5, 16, 32, 4, 16, 9.0),    # a padded row in the last block
    (5, 16, 16, 1, 16, 2.0),    # one row a block
])
def test_dtj_join_equals_filter_delta_t(T, M, bp, bc, bm, delta_t, use_index):
    """The refined kernel, dense and pruned, is ``filter_delta_t`` of the
    plain kernel bit for bit, and of the jnp join up to the weights'
    rounding; its counts are the matches before and after."""
    rng = np.random.default_rng(T * 100 + M + bp)
    b = _rand_batch(rng, T, M, pad_row=1)
    join, matches = stjoin_ops.subtrajectory_join(
        b, b, 2.5, 12.0, delta_t, use_index=use_index, bp=bp, bc=bc, bm=bm)
    plain, _ = stjoin_ops.best_match_join_kernel(b, b, 2.5, 12.0, bp=bp,
                                                 bc=bc, bm=bm)
    want = geometry.filter_delta_t(plain, b.t, delta_t)
    assert (np.asarray(join.best_w) == np.asarray(want.best_w)).all()
    assert (np.asarray(join.best_idx) == np.asarray(want.best_idx)).all()
    jnp_join = geometry.filter_delta_t(
        geometry.best_match_join(b, b, 2.5, 12.0), b.t, delta_t)
    assert (np.asarray(join.best_idx) == np.asarray(jnp_join.best_idx)).all()
    np.testing.assert_allclose(np.asarray(join.best_w),
                               np.asarray(jnp_join.best_w), atol=1e-5)
    before = int((np.asarray(plain.best_w) > 0).sum())
    after = int((np.asarray(want.best_w) > 0).sum())
    assert 0 < after < before
    assert np.asarray(matches).tolist() == [before, after]


def _hand_batch():
    """Three tracks of 8 fixes, one a minute.  Track 1 runs 0.3 beside
    track 0 the whole way; track 2 comes 0.3 to its other side at fixes 3
    and 4 only (0.6 from track 1) and is far away otherwise."""
    M = 8
    x = np.tile(np.arange(M, dtype=np.float32), (3, 1))
    y = np.zeros((3, M), np.float32)
    y[1] = 0.3
    y[2] = 50.0
    y[2, 3:5] = -0.3
    t = np.tile(np.arange(M, dtype=np.float32) * 60.0, (3, 1))
    return TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y),
                           t=jnp.asarray(t),
                           valid=jnp.ones((3, M), bool),
                           traj_id=jnp.arange(3, dtype=jnp.int32))


@pytest.mark.parametrize("use_index", [False, True])
def test_dtj_join_matches_a_hand_count(use_index):
    """Tracks 0 and 1 match at all 8 fixes each way, a run from the
    block's first fix to its last (two rows a block), 420 s; tracks 0
    and 2 at fixes 3-4 each way, 60 s.  Before the refine 8 + 8 + 2 + 2
    matches, after it at 120 s the 16 of the long runs."""
    b = _hand_batch()
    join, matches = stjoin_ops.subtrajectory_join(
        b, b, 0.5, 30.0, 120.0, use_index=use_index, bp=16, bc=1, bm=8)
    assert np.asarray(matches).tolist() == [20, 16]
    kept = np.asarray(join.best_w) > 0
    assert kept[0, :, 1].all() and kept[1, :, 0].all()
    assert not kept[:, :, 2].any() and not kept[2].any()
    assert (np.asarray(join.best_idx)[0, :, 1] == np.arange(8)).all()


def test_dtj_join_at_delta_t_zero_is_the_plain_kernel():
    """At delta_t 0 the join compiles the plain kernel, returns no counts
    and equals ``best_match_join_kernel`` bit for bit."""
    import jax
    b = _hand_batch()
    join, matches = stjoin_ops.subtrajectory_join(
        b, b, 0.5, 30.0, 0.0, bp=16, bc=1, bm=8)
    assert matches is None
    plain, _ = stjoin_ops.best_match_join_kernel(b, b, 0.5, 30.0, bp=16,
                                                 bc=1, bm=8)
    assert (np.asarray(join.best_w) == np.asarray(plain.best_w)).all()
    assert (np.asarray(join.best_idx) == np.asarray(plain.best_idx)).all()
    text = str(jax.make_jaxpr(
        lambda b: stjoin_ops.subtrajectory_join(b, b, 0.5, 30.0, 0.0,
                                                bp=16, bc=1, bm=8))(b))
    assert "stjoin_pallas" in text and "stjoin_dtj_pallas" not in text
    text = str(jax.make_jaxpr(
        lambda b: stjoin_ops.subtrajectory_join(b, b, 0.5, 30.0, 1.0,
                                                bp=16, bc=1, bm=8))(b))
    assert "stjoin_dtj_pallas" in text


def test_dtj_row_block_is_whole_rows():
    assert stjoin_ops._row_block(2048, 128, 256, 120.0) == 256
    assert stjoin_ops._row_block(2048, 24, 256, 1.0) == 240
    assert stjoin_ops._row_block(3, 24, 256, 1.0) == 72
    assert stjoin_ops._row_block(8, 300, 256, 1.0) == 300
    assert stjoin_ops._row_block(8, 24, 256, 0.0) == 256


def test_run_dsc_returns_the_join_counts():
    """``DSCOutput.join_matches`` on the index-pruned kernel join: the
    counts of ``subtrajectory_join``; None at delta_t 0 and on the jnp
    join."""
    from repro.core.plan import EnginePlan
    rng = np.random.default_rng(5)
    b = _rand_batch(rng, 8, 16)
    params = DSCParams(eps_sp=2.5, eps_t=12.0, delta_t=6.0, w=3, tau=0.2,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    plan = EnginePlan(mode="materialize", use_kernel=True, use_index=True,
                      seg_use_kernel=True)
    out = run_dsc(b, params, plan=plan)
    _, want = stjoin_ops.subtrajectory_join(b, b, 2.5, 12.0, 6.0,
                                            use_index=True)
    assert np.asarray(out.join_matches).tolist() == np.asarray(want).tolist()
    ref = run_dsc(b, params)
    assert ref.join_matches is None
    for f in ("member_of", "is_rep", "is_outlier"):
        assert (np.asarray(getattr(out.result, f))
                == np.asarray(getattr(ref.result, f))).all()
    assert run_dsc(b, params.replace(delta_t=0.0),
                   plan=plan).join_matches is None


# ---------------------------------------------------------------------------
# Pass 2 from the materialized cube: the sweep's raw scatter, bit for bit
# ---------------------------------------------------------------------------


def _random_seg(rng, valid, max_subs, p_cut):
    """A segmentation as ``segmentation`` builds it, from random cuts:
    sub-slots nondecreasing along each row's valid points, clipped at
    ``max_subs - 1``, -1 on invalid points."""
    cut = jnp.asarray(rng.uniform(0, 1, valid.shape) < p_cut)
    return segmentation._finalize(cut, valid, jnp.zeros(valid.shape),
                                  max_subs).sub_local


def _cube(b, c, delta_t, source, use_index):
    """The join cube of ``b`` against ``c``: ``"pallas"`` the plain
    kernel, ``"filter"`` the plain kernel refined by ``filter_delta_t``,
    ``"dtj"`` the kernel with the refine inside (also at ``delta_t`` 0)."""
    from repro.core.types import JoinResult
    from repro.kernels.stjoin.stjoin import stjoin_dtj_pallas
    if source == "dtj" and delta_t == 0:
        T, M = b.x.shape
        C = c.x.shape[0]
        bc, bm = stjoin_ops._join_geometry(C, c.x.shape[1], 128, 128)
        bp = stjoin_ops._row_block(T, M, 256, 1.0)
        ref_ops, cand_ops = stjoin_ops._padded_operands(b, c, bp, bc, bm)
        w, idx, _ = stjoin_dtj_pallas(*ref_ops, *cand_ops, 2.5, 12.0, 0.0,
                                      rows=bp // M, M=M, bc=bc, bm=bm)
        return JoinResult(best_w=w[:T * M, :C].reshape(T, M, C),
                          best_idx=idx[:T * M, :C].reshape(T, M, C))
    join, _ = stjoin_ops.subtrajectory_join(
        b, c, 2.5, 12.0, delta_t if source == "dtj" else 0.0,
        use_index=use_index)
    if source == "filter":
        join = geometry.filter_delta_t(join, b.t, delta_t)
    return join


def _plant_ties(rng, c, n):
    """Copy fix ``j`` of ``n`` candidate rows onto fix ``j + 1``, so that
    both weigh the same against every ref point; returns the batch and
    the ``(row, j)`` pairs."""
    x, y, t = (np.array(a) for a in (c.x, c.y, c.t))
    v = np.array(c.valid)
    rows = rng.choice(c.x.shape[0], n, replace=False)
    js = rng.integers(0, c.x.shape[1] - 1, n)
    for r, j in zip(rows, js):
        for a in (x, y, t):
            a[r, j + 1] = a[r, j]
        v[r, j] = v[r, j + 1] = True
    return TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y),
                           t=jnp.asarray(t), valid=jnp.asarray(v),
                           traj_id=c.traj_id), list(zip(rows, js))


@pytest.mark.parametrize("T,M,C,Mc,rows,bc,bm,delta_t,source,use_index", [
    (4, 16, 8, 16, 2, 8, 16, 0.0, "pallas", False),  # whole tiles, dense
    (4, 16, 8, 16, 2, 8, 16, 0.0, "pallas", True),   # an index plan
    (5, 17, 7, 13, 3, 8, 8, 0.0, "pallas", True),    # T, C and Mc ragged
    (5, 17, 7, 13, 3, 8, 8, 6.0, "dtj", False),
    (5, 17, 7, 13, 3, 8, 8, 6.0, "dtj", True),
    (6, 16, 9, 12, 2, 4, 8, 4.0, "filter", True),
    (6, 16, 9, 12, 2, 4, 8, 0.0, "dtj", False),      # the refine keeps all
    (3, 40, 35, 11, 2, 32, 128, 0.0, "pallas", True),  # bc 32, bm > Mc
])
def test_cube_pass2_equals_the_sweep(T, M, C, Mc, rows, bc, bm, delta_t,
                                     source, use_index):
    """``stjoin_sim_cube`` over the join's cube gives ``stjoin_sim_fused``'s
    raw similarity bit for bit: plain and refined cubes, dense and index
    plans, ragged geometries, invalid and unsegmented ref points, padded
    rows, and exact weight ties across a sub-slot boundary."""
    max_subs = 4
    rng = np.random.default_rng(T * 1000 + C * 10 + int(delta_t))
    b = _rand_batch(rng, T, M, pad_row=0)
    c, ties = _plant_ties(rng, _rand_batch(rng, C, Mc), 3)
    ref_sub = np.array(_random_seg(rng, b.valid, max_subs, 0.15))
    ref_sub[rng.uniform(0, 1, ref_sub.shape) < 0.1] = -1  # unsegmented
    ref_sub = jnp.asarray(ref_sub)
    cut = rng.uniform(0, 1, c.valid.shape) < 0.2
    for r, j in ties:
        cut[r, j + 1] = True              # the tie spans a sub-slot cut
    cand_sub = segmentation._finalize(jnp.asarray(cut), c.valid,
                                      jnp.zeros(c.valid.shape),
                                      max_subs).sub_local
    join = _cube(b, c, delta_t, source, use_index)
    idx = np.asarray(join.best_idx)
    assert any((idx[:, :, r] == j).any() for r, j in ties)
    kw = dict(rows=rows, bc=bc, bm=bm)
    tiles = (stjoin_ops.plan_fused_tiles(b.x, b.y, b.t, b.valid, c.x, c.y,
                                         c.t, c.valid, 2.5, 12.0, **kw)
             if use_index else None)
    want = np.asarray(stjoin_ops.stjoin_sim_fused(
        b, c, ref_sub, cand_sub, max_subs, 2.5, 12.0, delta_t,
        tile_ids=tiles, **kw))
    got = np.asarray(stjoin_ops.stjoin_sim_cube(
        join, ref_sub, cand_sub, max_subs, tile_ids=tiles, **kw))
    assert want.sum() > 0
    assert got.shape == want.shape
    assert (got.view(np.uint32) == want.view(np.uint32)).all()


@pytest.mark.parametrize("seed,max_subs,p_cut", [
    (0, 4, 0.05),     # few cuts: candidates with fewer than max_subs slots
    (1, 4, 0.6),      # many cuts: the last slot clipped at max_subs - 1
    (2, 8, 0.2),
    (3, 1, 0.3),      # one slot: every match is slot 0
])
def test_cube_sub_slot_map_equals_the_gather(seed, max_subs, p_cut):
    """The sub-slot from ``cand_starts``'s boundaries is the sub-slot of
    the matched point, ``take_along_axis`` of the candidate's sub-slots,
    and -1 where nothing matched."""
    from repro.kernels.stjoin.stjoin import _cube_sub
    rng = np.random.default_rng(seed)
    C, Mc, P = 24, 20, 64
    valid = jnp.asarray(rng.uniform(0, 1, (C, Mc)) > 0.2)
    cand_sub = _random_seg(rng, valid, max_subs, p_cut)
    # a matched point is a valid one; -1 where nothing matched
    pick = rng.integers(0, Mc, (P, C))
    ok = np.asarray(valid)[np.arange(C)[None, :], pick]
    idx = np.where(ok & (rng.uniform(0, 1, (P, C)) > 0.2), pick, -1)
    got = np.asarray(_cube_sub(jnp.asarray(idx, jnp.int32),
                               stjoin_ops.cand_starts(cand_sub, max_subs)))
    want = np.take_along_axis(np.asarray(cand_sub).T,
                              np.maximum(idx, 0), axis=0)
    want = np.where(idx < 0, -1, want)
    assert (idx < 0).any() and (got == want).all()
    n_subs = np.asarray(cand_sub).max(axis=1) + 1
    if p_cut < 0.1:
        assert (n_subs < max_subs).any()
    if p_cut > 0.5:
        assert (want == max_subs - 1).any()


@pytest.mark.parametrize("segmentation_,delta_t", [("tsa1", 0.0),
                                                   ("tsa2", 0.3)])
def test_run_dsc_cube_pass2_equals_fused(fig1, segmentation_, delta_t):
    """``run_dsc`` on the kernel join in materialize mode (pass 2 from the
    cube) and in fused mode (pass 2 by the sweep): the same segmentation,
    similarity, labels and scores."""
    from repro.core.plan import EnginePlan
    batch, _ = fig1
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=delta_t, w=6,
                       tau=0.15, alpha_sigma=-1.0, k_sigma=-1.0,
                       segmentation=segmentation_)
    cube = run_dsc(batch, params, plan=EnginePlan(
        mode="materialize", use_kernel=True, use_index=True))
    fused = run_dsc(batch, params, plan=EnginePlan(mode="fused",
                                                   use_index=True))
    assert cube.join is not None and fused.join is None
    assert (np.asarray(cube.seg.sub_local)
            == np.asarray(fused.seg.sub_local)).all()
    assert (np.asarray(cube.sim) == np.asarray(fused.sim)).all()
    for f in ("member_of", "is_rep", "is_outlier"):
        assert (np.asarray(getattr(cube.result, f))
                == np.asarray(getattr(fused.result, f))).all()
    assert float(cube.sscr) == float(fused.sscr)
    assert float(cube.rmse) == float(fused.rmse)


@pytest.mark.parametrize("plan_kw,want", [
    (dict(mode="materialize", use_kernel=True, use_index=True), "cube"),
    (dict(mode="materialize", use_kernel=True), "cube"),
    (dict(mode="fused", use_index=True), "sweep"),
    (dict(mode="materialize", use_kernel=True, sim_mode="topk"), "sweep"),
    (dict(mode="materialize"), "scatter"),
])
def test_run_dsc_span_names_its_pass2(tmp_path, plan_kw, want):
    """The host span ``dsc.run`` carries ``pass2``: which pass 2 built
    the similarity."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from repro.core.plan import EnginePlan
    rng = np.random.default_rng(3)
    b = _rand_batch(rng, 6, 16)
    params = DSCParams(eps_sp=2.5, eps_t=12.0, w=3, tau=0.2,
                       alpha_sigma=-1.0, k_sigma=-1.0)
    plan = EnginePlan(**plan_kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(run_dsc(b, params, plan=plan).result)
    finally:
        jax.profiler.stop_trace()
    f, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                   recursive=True)
    host = next(p for p in ProfileData.from_file(f).planes
                if p.name == "/host:CPU")
    runs = [dict(ev.stats) for ln in host.lines for ev in ln.events
            if ev.name == "dsc.run"]
    assert [r["pass2"] for r in runs] == [want]
