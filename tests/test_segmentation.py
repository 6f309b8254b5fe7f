"""TSA1/TSA2 property tests: valid partitions, step-change detection."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.segmentation import (_local_max_cuts, _window_overlap_counts,
                                     _window_overlap_counts_bitplane,
                                     _windowed_union, tsa1, tsa2, tsa2_signal)
from repro.core.voting import neighbor_mask_packed
from repro.core.types import JoinResult


def _pack_bools(matched: np.ndarray) -> jnp.ndarray:
    """[T, M, C] bool -> [T, M, ceil(C/32)] uint32 (same layout as
    ``voting.neighbor_mask_packed``); C need not be a multiple of 32."""
    T, M, C = matched.shape
    W = -(-C // 32)
    pad = np.zeros((T, M, W * 32 - C), bool)
    bits = np.concatenate([matched, pad], axis=-1).reshape(T, M, W, 32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))
    return jnp.asarray((bits.astype(np.uint32) * weights).sum(-1,
                                                              dtype=np.uint32))


def test_tsa1_detects_step_change():
    """A clean step in the voting signal yields exactly one interior cut at
    the step position."""
    M, w = 64, 6
    sig = np.concatenate([np.ones(32), 0.2 * np.ones(32)])[None, :]
    valid = np.ones((1, M), bool)
    seg = tsa1(jnp.asarray(sig, jnp.float32), jnp.asarray(valid), w, 0.3, 8)
    cuts = np.nonzero(np.asarray(seg.cut)[0])[0]
    assert list(cuts[:1]) == [0]
    interior = [c for c in cuts if c > 0]
    assert len(interior) == 1 and abs(interior[0] - 32) <= 1
    assert int(seg.num_subs[0]) == 2


def test_tsa1_flat_signal_no_cuts():
    M, w = 64, 6
    sig = 0.7 * np.ones((1, M))
    valid = np.ones((1, M), bool)
    seg = tsa1(jnp.asarray(sig, jnp.float32), jnp.asarray(valid), w, 0.2, 8)
    assert int(seg.num_subs[0]) == 1


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_tsa1_partition_validity(seed):
    """Subtrajectory labels are a monotone non-decreasing partition of the
    valid prefix; padding labelled -1; num_subs consistent."""
    rng = np.random.default_rng(seed)
    T, M, w = 3, 48, 5
    sig = rng.uniform(0, 1, (T, M)).astype(np.float32)
    count = rng.integers(10, M + 1, T)
    valid = np.arange(M)[None, :] < count[:, None]
    seg = tsa1(jnp.asarray(sig), jnp.asarray(valid), w, 0.25, 8)
    sl = np.asarray(seg.sub_local)
    for r in range(T):
        labs = sl[r][valid[r]]
        assert labs[0] == 0
        assert (np.diff(labs) >= 0).all() and (np.diff(labs) <= 1).all()
        assert (sl[r][~valid[r]] == -1).all()
        assert int(seg.num_subs[r]) == labs.max() + 1


def test_tsa2_detects_composition_change():
    """Neighbor set flips completely at midpoint with constant density ->
    TSA2 cuts, TSA1 does not (Example 2)."""
    T, M, C = 1, 64, 64
    w = 6
    best_w = np.zeros((T, M, C), np.float32)
    best_w[0, :32, :8] = 0.9       # first half: neighbors 0..7
    best_w[0, 32:, 8:16] = 0.9     # second half: neighbors 8..15
    join = JoinResult(best_w=jnp.asarray(best_w),
                      best_idx=jnp.zeros((T, M, C), jnp.int32))
    masks = neighbor_mask_packed(join)
    valid = jnp.ones((T, M), bool)
    seg2 = tsa2(masks, valid, w, 0.4, 8)
    assert int(seg2.num_subs[0]) == 2
    cuts = np.nonzero(np.asarray(seg2.cut)[0])[0]
    assert abs([c for c in cuts if c > 0][0] - 32) <= 1
    # density signal is flat -> TSA1 sees nothing
    vote = jnp.asarray(best_w.sum(-1) / best_w.sum(-1).max())
    seg1 = tsa1(vote, valid, w, 0.4, 8)
    assert int(seg1.num_subs[0]) == 1


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_tsa2_partition_validity(seed):
    rng = np.random.default_rng(seed)
    T, M, W, w = 2, 40, 2, 4
    masks = jnp.asarray(rng.integers(0, 2 ** 31, (T, M, W)).astype(np.uint32))
    count = rng.integers(12, M + 1, T)
    valid = jnp.asarray(np.arange(M)[None, :] < count[:, None])
    seg = tsa2(masks, valid, w, 0.3, 8)
    sl = np.asarray(seg.sub_local)
    v = np.asarray(valid)
    for r in range(T):
        labs = sl[r][v[r]]
        assert labs[0] == 0
        assert (np.diff(labs) >= 0).all() and (np.diff(labs) <= 1).all()


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_tsa2_packed_and_chunked_match_full_expansion(seed):
    """Both production-history paths — the packed windowed-OR engine and
    the retained bit-plane chunked fold — must equal the all-at-once
    ``[T, M, W*32]`` expansion bit for bit."""
    rng = np.random.default_rng(seed)
    T, M, W, w = 2, 36, 3, 5
    masks = jnp.asarray(rng.integers(0, 2 ** 31, (T, M, W)).astype(np.uint32))

    n = jnp.arange(M)
    l1 = _windowed_union(masks, n - w, n - 1)        # full [T, M, W*32]
    l2 = _windowed_union(masks, n, n + w - 1)
    want_inter = np.asarray(jnp.sum(l1 & l2, axis=-1))
    want_union = np.asarray(jnp.sum(l1 | l2, axis=-1))
    for impl in (_window_overlap_counts, _window_overlap_counts_bitplane):
        inter, union = impl(masks, w)
        assert (np.asarray(inter) == want_inter).all(), impl.__name__
        assert (np.asarray(union) == want_union).all(), impl.__name__


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_packed_windowed_or_vs_bitplane_oracle_property(seed):
    """The packed-word engine equals the pinned bit-plane oracle across
    the edge cases the block OR-scan has to get right: w=1, w >= M,
    all-padding (zero-mask) rows, and C not a multiple of 32."""
    rng = np.random.default_rng(seed)
    T = int(rng.integers(1, 4))
    M = int(rng.integers(2, 48))
    C = int(rng.integers(1, 100))          # frequently not a multiple of 32
    w = int(rng.choice([1, 2, 3, M, M + 4]))
    matched = rng.uniform(0, 1, (T, M, C)) < 0.3
    matched[0] = False                     # an all-padding trajectory
    masks = _pack_bools(matched)

    ip, up = _window_overlap_counts(masks, w)
    ib, ub = _window_overlap_counts_bitplane(masks, w)
    assert (np.asarray(ip) == np.asarray(ib)).all(), (seed, w)
    assert (np.asarray(up) == np.asarray(ub)).all(), (seed, w)

    d_p = np.asarray(tsa2_signal(masks, w))
    d_b = np.asarray(tsa2_signal(masks, w, impl="bitplane"))
    assert (d_p == d_b).all(), (seed, w)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_tsa2_kernel_matches_jnp_engine(seed):
    """tsa2(use_kernel=True) — the fused Pallas segmentation kernel — is
    bit-identical to the jnp packed engine: cuts, labels, and score."""
    rng = np.random.default_rng(seed)
    T, M, W = 3, 40, 2
    w = int(rng.integers(1, 8))
    masks = jnp.asarray(rng.integers(0, 2 ** 31, (T, M, W)).astype(np.uint32))
    count = rng.integers(4, M + 1, T)
    valid = jnp.asarray(np.arange(M)[None, :] < count[:, None])
    a = tsa2(masks, valid, w, 0.3, 8)
    b = tsa2(masks, valid, w, 0.3, 8, use_kernel=True)
    for f in ("cut", "sub_local", "num_subs", "score"):
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), (seed, w, f)


@pytest.mark.parametrize("mode", ["materialize", "fused"])
def test_tsa2_end_to_end_seg_kernel_parity(fig1, fig1_params, mode):
    """run_dsc with plan.seg_use_kernel: bit-identical TSA2 cut masks,
    segmentations, and downstream cluster labels in both join modes."""
    from repro.core.dsc import run_dsc
    from repro.core.plan import EnginePlan
    batch, _ = fig1
    a = run_dsc(batch, fig1_params, plan=EnginePlan(mode=mode))
    b = run_dsc(batch, fig1_params,
                plan=EnginePlan(mode=mode, seg_use_kernel=True))
    for f in ("cut", "sub_local", "num_subs"):
        assert np.array_equal(np.asarray(getattr(a.seg, f)),
                              np.asarray(getattr(b.seg, f))), (mode, f)
    for f in ("member_of", "is_rep", "is_outlier"):
        assert np.array_equal(np.asarray(getattr(a.result, f)),
                              np.asarray(getattr(b.result, f))), (mode, f)


def _local_max_cuts_stacked(d, valid, w, tau, count):
    """The former implementation of ``_local_max_cuts``: materializes all
    2w-1 shifted copies as a ``[T, M, 2w-1]`` stack.  Kept here as the
    regression oracle for the O(M) prefix/suffix cummax rewrite."""
    T, M = d.shape
    n = jnp.arange(M)
    admissible = (n[None, :] >= w) & (n[None, :] <= count[:, None] - w - 1)
    d = jnp.where(valid & admissible, d, -jnp.inf)

    neg_inf = -jnp.inf
    pads = w - 1
    dp = jnp.pad(d, ((0, 0), (pads, pads)), constant_values=neg_inf)
    windows = jnp.stack(
        [dp[:, k:k + M] for k in range(2 * pads + 1)], axis=-1)
    wmax = jnp.max(windows, axis=-1)
    left = (jnp.max(windows[..., :pads], axis=-1) if pads > 0
            else jnp.full_like(d, neg_inf))
    is_max = (d >= wmax) & (d > left)
    return is_max & (d > tau) & admissible & valid


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_local_max_cuts_cummax_matches_stacked(seed):
    """The prefix/suffix cummax sliding max must reproduce the stacked
    2w-1-copies formulation bit for bit — including duplicate d values
    (strict-left tie break) and masked/-inf positions."""
    rng = np.random.default_rng(seed)
    T, M = 3, 57                                  # non-multiple of any block
    # quantized signal -> frequent exact ties inside windows
    d = jnp.asarray(rng.integers(0, 6, (T, M)).astype(np.float32) / 5.0)
    count = rng.integers(5, M + 1, T)
    valid = jnp.asarray(np.arange(M)[None, :] < count[:, None])
    count = jnp.asarray(count.astype(np.int32))
    for w in (1, 2, 5, 11):
        got = _local_max_cuts(d, valid, w, 0.25, count)
        want = _local_max_cuts_stacked(d, valid, w, 0.25, count)
        assert np.array_equal(np.asarray(got), np.asarray(want)), (seed, w)


def test_max_subs_clipping():
    """Pathological signal with many steps respects max_subtrajs_per_traj."""
    M, w = 128, 3
    sig = (np.arange(M) // 8 % 2).astype(np.float32)[None, :]
    valid = np.ones((1, M), bool)
    seg = tsa1(jnp.asarray(sig), jnp.asarray(valid), w, 0.1, 4)
    assert int(seg.num_subs[0]) <= 4
    assert np.asarray(seg.sub_local).max() <= 3
