"""Per-kernel validation: shape/dtype sweeps + allclose vs the ref.py oracle,
plus hypothesis property tests on the kernels' invariants."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.segmentation import tsa2_signal
from repro.kernels import RATIO_MAX_COUNT, exact_ratio, round_quotient
from repro.kernels.jaccard.ops import window_jaccard
from repro.kernels.jaccard.ref import jaccard_ref
from repro.kernels.lcss.ops import lcss_scores, lcss_similarity
from repro.kernels.lcss.ref import lcss_ref
from repro.kernels.stjoin.ops import best_match_join_kernel
from repro.kernels.stjoin.ref import stjoin_ref


def _rand_points(rng, T, M):
    x = rng.uniform(0, 10, (T, M)).astype(np.float32)
    y = rng.uniform(0, 10, (T, M)).astype(np.float32)
    t = np.sort(rng.uniform(0, 50, (T, M)), axis=1).astype(np.float32)
    v = rng.uniform(0, 1, (T, M)) > 0.15
    ids = np.arange(T, dtype=np.int32)
    return x, y, t, v, ids


# ----------------------------- stjoin ---------------------------------------

@pytest.mark.parametrize("T,M,C,Mc,bp,bc,bm", [
    (8, 32, 8, 32, 64, 4, 32),
    (4, 64, 8, 16, 32, 8, 16),
    (16, 16, 4, 64, 256, 2, 32),
    (3, 24, 5, 40, 8, 1, 8),       # ragged -> exercises padding
])
def test_stjoin_shapes(T, M, C, Mc, bp, bc, bm):
    rng = np.random.default_rng(T * 100 + M)
    rx, ry, rt, rv, rid = _rand_points(rng, T, M)
    cx, cy, ct, cv, cid = _rand_points(rng, C, Mc)
    from repro.core.types import TrajectoryBatch
    ref_b = TrajectoryBatch(x=jnp.asarray(rx), y=jnp.asarray(ry),
                            t=jnp.asarray(rt), valid=jnp.asarray(rv),
                            traj_id=jnp.asarray(rid))
    cand_b = TrajectoryBatch(x=jnp.asarray(cx), y=jnp.asarray(cy),
                             t=jnp.asarray(ct), valid=jnp.asarray(cv),
                             traj_id=jnp.asarray(cid))
    got, _ = best_match_join_kernel(ref_b, cand_b, 2.0, 10.0,
                                    bp=bp, bc=bc, bm=bm)
    want_w, want_idx = stjoin_ref(
        jnp.asarray(rx.reshape(-1)), jnp.asarray(ry.reshape(-1)),
        jnp.asarray(rt.reshape(-1)),
        jnp.asarray(np.repeat(rid, M)), jnp.asarray(rv.reshape(-1)),
        jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(ct),
        jnp.asarray(cid), jnp.asarray(cv), 2.0, 10.0)
    np.testing.assert_allclose(np.asarray(got.best_w).reshape(T * M, C),
                               np.asarray(want_w), atol=1e-5)
    assert (np.asarray(got.best_idx).reshape(T * M, C)
            == np.asarray(want_idx)).all()


def test_stjoin_symmetry_of_matching():
    """If r_i matches some point of trajectory s, then s has a point whose
    best match set includes r's trajectory (cylinder symmetry)."""
    rng = np.random.default_rng(7)
    x, y, t, v, ids = _rand_points(rng, 6, 32)
    from repro.core.types import TrajectoryBatch
    b = TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y), t=jnp.asarray(t),
                        valid=jnp.asarray(v), traj_id=jnp.asarray(ids))
    got, _ = best_match_join_kernel(b, b, 3.0, 10.0, bp=8, bc=2, bm=8)
    w = np.asarray(got.best_w)          # [T, M, C]
    pair = w.sum(axis=1) > 0            # [T, C] r matched c somewhere
    assert (pair == pair.T).all()


def test_stjoin_excludes_self():
    rng = np.random.default_rng(3)
    x, y, t, v, ids = _rand_points(rng, 4, 16)
    from repro.core.types import TrajectoryBatch
    b = TrajectoryBatch(x=jnp.asarray(x), y=jnp.asarray(y), t=jnp.asarray(t),
                        valid=jnp.asarray(v), traj_id=jnp.asarray(ids))
    got, _ = best_match_join_kernel(b, b, 100.0, 1e9, bp=8, bc=2, bm=8)
    w = np.asarray(got.best_w)
    for r in range(4):
        assert (w[r, :, r] == 0).all()


# ----------------------------- lcss -----------------------------------------

@pytest.mark.parametrize("B,N,M", [(2, 16, 16), (3, 8, 24), (1, 33, 17)])
def test_lcss_matches_ref(B, N, M):
    rng = np.random.default_rng(B * 7 + N)
    rx = jnp.asarray(rng.uniform(0, 5, (B, N)), jnp.float32)
    ry = jnp.asarray(rng.uniform(0, 5, (B, N)), jnp.float32)
    rt = jnp.asarray(np.sort(rng.uniform(0, 50, (B, N)), 1), jnp.float32)
    rv = jnp.asarray(rng.uniform(0, 1, (B, N)) > 0.1)
    sx = jnp.asarray(rng.uniform(0, 5, (B, M)), jnp.float32)
    sy = jnp.asarray(rng.uniform(0, 5, (B, M)), jnp.float32)
    stm = jnp.asarray(np.sort(rng.uniform(0, 50, (B, M)), 1), jnp.float32)
    sv = jnp.asarray(rng.uniform(0, 1, (B, M)) > 0.1)
    want = np.maximum(np.asarray(
        lcss_ref(rx, ry, rt, rv, sx, sy, stm, sv, 2.0, 25.0)), 0.0)
    got = np.asarray(lcss_scores(rx, ry, rt, rv, sx, sy, stm, sv, 2.0, 25.0))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_lcss_identical_sequences_full_score():
    rng = np.random.default_rng(0)
    N = 24
    x = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    y = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    t = jnp.asarray(np.sort(rng.uniform(0, 50, (1, N)), 1), jnp.float32)
    v = jnp.ones((1, N), bool)
    sim = np.asarray(lcss_similarity(x, y, t, v, x, y, t, v, 2.0, 25.0))
    np.testing.assert_allclose(sim[0, 0], 1.0, atol=1e-5)  # weighted
    np.testing.assert_allclose(sim[0, 1], 1.0, atol=1e-5)  # classic


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_lcss_symmetry(seed):
    rng = np.random.default_rng(seed)
    N, M = 12, 12
    rx = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    ry = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    rt = jnp.asarray(np.sort(rng.uniform(0, 20, (1, N)), 1), jnp.float32)
    sx = jnp.asarray(rng.uniform(0, 5, (1, M)), jnp.float32)
    sy = jnp.asarray(rng.uniform(0, 5, (1, M)), jnp.float32)
    stm = jnp.asarray(np.sort(rng.uniform(0, 20, (1, M)), 1), jnp.float32)
    v = jnp.ones((1, N), bool)
    a = np.asarray(lcss_scores(rx, ry, rt, v, sx, sy, stm, v, 2.0, 10.0))
    b = np.asarray(lcss_scores(sx, sy, stm, v, rx, ry, rt, v, 2.0, 10.0))
    np.testing.assert_allclose(a, b, atol=1e-4)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_lcss_bounds(seed):
    """0 <= weighted score <= classic count <= min(n, m)."""
    rng = np.random.default_rng(seed)
    N, M = 10, 14
    rx = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    ry = jnp.asarray(rng.uniform(0, 5, (1, N)), jnp.float32)
    rt = jnp.asarray(np.sort(rng.uniform(0, 20, (1, N)), 1), jnp.float32)
    rv = jnp.asarray(rng.uniform(0, 1, (1, N)) > 0.2)
    sx = jnp.asarray(rng.uniform(0, 5, (1, M)), jnp.float32)
    sy = jnp.asarray(rng.uniform(0, 5, (1, M)), jnp.float32)
    stm = jnp.asarray(np.sort(rng.uniform(0, 20, (1, M)), 1), jnp.float32)
    sv = jnp.asarray(rng.uniform(0, 1, (1, M)) > 0.2)
    s = np.asarray(lcss_scores(rx, ry, rt, rv, sx, sy, stm, sv, 2.0, 10.0))[0]
    n = int(np.asarray(rv).sum())
    m = int(np.asarray(sv).sum())
    assert 0.0 <= s[0] <= s[1] + 1e-5
    assert s[1] <= min(n, m) + 1e-5


# ----------------------------- jaccard --------------------------------------

@pytest.mark.parametrize("T,M,W,w", [(4, 32, 1, 4), (8, 64, 3, 7),
                                     (2, 128, 2, 16), (5, 40, 4, 5)])
def test_jaccard_matches_ref(T, M, W, w):
    rng = np.random.default_rng(T + M + W)
    masks = jnp.asarray(
        rng.integers(0, 2 ** 31, (T, M, W)).astype(np.uint32))
    valid = jnp.asarray(rng.uniform(0, 1, (T, M)) > 0.1)
    masked = jnp.where(valid[..., None], masks, jnp.uint32(0))
    want = np.asarray(jaccard_ref(masked, w))
    got = np.asarray(window_jaccard(masks, valid, w=w))
    np.testing.assert_allclose(got, want, atol=1e-6)


@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 8))
@settings(max_examples=10, deadline=None)
def test_jaccard_range_and_identical_windows(seed, w):
    rng = np.random.default_rng(seed)
    T, M, W = 2, 32, 2
    # constant masks -> identical windows -> d == 0 in the interior
    row = rng.integers(0, 2 ** 31, (1, 1, W)).astype(np.uint32)
    masks = jnp.asarray(np.broadcast_to(row, (T, M, W)).copy())
    valid = jnp.ones((T, M), bool)
    d = np.asarray(window_jaccard(masks, valid, w=w))
    assert (d >= 0).all() and (d <= 1).all()
    assert np.allclose(d[:, w:M - w], 0.0, atol=1e-6)


@pytest.mark.parametrize("off", [-2, -1, 0, 1, 2])
def test_round_quotient_recovers_the_correctly_rounded_ratio(off):
    """A quotient up to two ulps off (as the TPU's division can be) comes
    back as the IEEE quotient, for every ``num <= den`` up to 1024, for a
    sample of counts up to ``RATIO_MAX_COUNT``, and for the ratios of the
    largest 128 denominators that lie nearest a midpoint between two
    float32 values, where a residual off by an ulp would pick wrong."""
    n = np.arange(0, 1025, dtype=np.float32)
    num, den = np.meshgrid(n, n[1:], indexing="ij")
    keep = num <= den
    rng = np.random.default_rng(15)
    big = rng.integers(1025, RATIO_MAX_COUNT + 1, 200_000).astype(np.float32)
    part = np.floor(big * rng.uniform(0, 1, big.size)).astype(np.float32)
    hd = np.arange(RATIO_MAX_COUNT - 127, RATIO_MAX_COUNT + 1)[:, None]
    hn = np.arange(1, RATIO_MAX_COUNT + 1)[None, :]
    v = hn / hd
    q32 = v.astype(np.float32)
    ulp = np.spacing(q32).astype(np.float64)
    to_mid = np.where(hn <= hd, np.abs(np.abs(v - q32) - ulp / 2) / ulp,
                      np.inf)
    hard = np.unravel_index(np.argsort(to_mid, axis=None)[:20_000],
                            to_mid.shape)
    num = np.concatenate([num[keep], part,
                          hn[0, hard[1]].astype(np.float32)])
    den = np.concatenate([den[keep], big, hd[hard[0], 0].astype(np.float32)])
    want = num / den
    q = (want.view(np.int32) + off).view(np.float32)
    q = np.where(num == 0, np.float32(0), q)
    got = np.asarray(jax.jit(round_quotient)(num, den, q))
    assert (got == want).all(), int((got != want).sum())


def test_exact_ratio_is_the_plain_quotient_above_its_bound():
    num = jnp.asarray([1.0, 7.0, 3.0], jnp.float32)
    den = jnp.asarray([3.0, 9.0, 11.0], jnp.float32)
    assert (np.asarray(exact_ratio(num, den, RATIO_MAX_COUNT + 32))
            == np.asarray(num / den)).all()


def test_jaccard_at_256_words_is_correctly_rounded():
    """8192 candidate tracks (the ``dsc_sis`` width): the kernel and the
    jnp engine give ``1 - q``, ``q`` the IEEE quotient of the counts."""
    rng = np.random.default_rng(256)
    T, M, W, w = 2, 24, 256, 3
    masks = rng.integers(0, 2 ** 32, (T, M, W), dtype=np.uint64)
    masks &= rng.integers(0, 2 ** 32, (T, M, W), dtype=np.uint64)
    masks = masks.astype(np.uint32)
    valid = np.ones((T, M), bool)
    got = np.asarray(window_jaccard(jnp.asarray(masks), jnp.asarray(valid),
                                    w=w))
    eng = np.asarray(tsa2_signal(jnp.asarray(masks), w))
    u1 = np.zeros_like(masks)
    u2 = np.zeros_like(masks)
    for k in range(1, w + 1):
        u1[:, k:] |= masks[:, :M - k]
    for k in range(w):
        u2[:, :M - k] |= masks[:, k:]
    inter = np.bitwise_count(u1 & u2).sum(-1).astype(np.float32)
    union = np.bitwise_count(u1 | u2).sum(-1).astype(np.float32)
    want = np.where(union > 0,
                    np.float32(1) - inter / np.maximum(union, 1),
                    np.float32(0))
    assert union.max() > 4096
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(eng, want)
