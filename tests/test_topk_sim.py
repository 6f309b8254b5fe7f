"""Panel-streamed top-K similarity + neighbor-list clustering (DESIGN.md §8).

The contract is *certified bit identity*: whenever the per-row spill
certificate reports zero overflow, every consumer of the ``TopKSim``
representation — thresholds, both clustering engines, the Pallas list
kernels, and the full pipeline — must equal the dense ``[S, S]`` path
bit for bit.  When K truncates a potential alpha-edge, the certificate
must say so.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.clustering import (cluster, cluster_rounds_topk,
                                   cluster_sequential, cluster_sequential_topk,
                                   resolve_thresholds,
                                   resolve_thresholds_from_moments)
from repro.core.similarity import (similarity_topk, topk_from_dense,
                                   topk_overflow)
from repro.core.types import DSCParams, SubtrajTable

FIELDS = ("member_of", "member_sim", "is_rep", "is_outlier")


def _instance(seed, S=24, density=0.5, tied_voting=False):
    rng = np.random.default_rng(seed)
    raw = rng.uniform(0, 1, (S, S)).astype(np.float32)
    sim = np.maximum(raw, raw.T) * (rng.uniform(0, 1, (S, S)) > density)
    sim = np.maximum(sim, sim.T)
    np.fill_diagonal(sim, 0.0)
    valid = rng.uniform(0, 1, S) > 0.1
    sim = sim * (valid[:, None] & valid[None, :])
    voting = (rng.integers(0, 3, S).astype(np.float32) if tied_voting
              else rng.uniform(0, 5, S).astype(np.float32))
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.asarray(voting),
        card=jnp.asarray(rng.integers(1, 20, S).astype(np.int32)),
        valid=jnp.asarray(valid),
        traj_row=jnp.arange(S, dtype=jnp.int32))
    return jnp.asarray(sim.astype(np.float32)), table


def _assert_identical(res_a, res_b, ctx=""):
    for f in FIELDS:
        a, b = np.asarray(getattr(res_a, f)), np.asarray(getattr(res_b, f))
        assert np.array_equal(a, b), (f, ctx, a, b)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_full_k_engines_match_dense_oracle(seed):
    """K = S cannot truncate: every top-K engine is bit-identical to the
    dense sequential oracle, overflow provably zero."""
    sim, table = _instance(seed, tied_voting=(seed % 2 == 0))
    S = table.num_slots
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    dense = cluster_sequential(sim, table, params)
    tk = topk_from_dense(sim, table, S)
    assert int(topk_overflow(tk, dense.alpha_used)) == 0
    _assert_identical(dense, cluster_sequential_topk(tk, table, params))
    _assert_identical(dense, cluster_rounds_topk(tk, table, params))
    _assert_identical(dense,
                      cluster_rounds_topk(tk, table, params,
                                          use_kernel=True))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_truncated_k_certified_or_flagged(seed):
    """Any K: either the spill certificate is clean and labels equal the
    dense oracle bit for bit, or overflow is flagged — never a silent
    divergence."""
    rng = np.random.default_rng(seed)
    sim, table = _instance(seed, density=0.8)       # sparse rows
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    dense = cluster_sequential(sim, table, params)
    for K in (2, 4, 8, 16):
        tk = topk_from_dense(sim, table, K)
        res = cluster_rounds_topk(tk, table, params)
        if int(topk_overflow(tk, res.alpha_used)) == 0:
            _assert_identical(dense, res, f"seed={seed} K={K}")
        else:
            pass                                     # flagged, no claim


def test_overflow_fires_on_truncated_alpha_edges():
    """A hub row with more alpha-edges than K must raise the counter."""
    S = 12
    sim = np.zeros((S, S), np.float32)
    sim[0, 1:9] = sim[1:9, 0] = 0.9                  # degree-8 hub
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.ones(S), card=jnp.ones(S, jnp.int32),
        valid=jnp.ones(S, bool), traj_row=jnp.arange(S, dtype=jnp.int32))
    tk = topk_from_dense(jnp.asarray(sim), table, 4)
    assert int(topk_overflow(tk, jnp.float32(0.5))) > 0
    tk_wide = topk_from_dense(jnp.asarray(sim), table, 8)
    assert int(topk_overflow(tk_wide, jnp.float32(0.5))) == 0


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_thresholds_bitwise_from_streamed_moments(seed):
    """alpha/k from the TopKSim row moments equal the dense
    ``resolve_thresholds`` bit for bit — whatever K is."""
    sim, table = _instance(seed)
    params = DSCParams(alpha_sigma=0.7, k_sigma=-0.3)
    a_d, k_d = resolve_thresholds(params, sim, table)
    tk = topk_from_dense(sim, table, 4)
    a_t, k_t = resolve_thresholds_from_moments(
        params, (tk.degree, tk.row_sum, tk.row_sumsq), table)
    assert float(a_d) == float(a_t)
    assert float(k_d) == float(k_t)


def test_dispatcher_routes_topk():
    sim, table = _instance(3)
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    tk = topk_from_dense(sim, table, table.num_slots)
    _assert_identical(cluster(tk, table, params, engine="sequential"),
                      cluster(tk, table, params, engine="rounds"))
    with pytest.raises(ValueError):
        cluster(tk, table, params, engine="bogus")


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_topk_kernel_primitives_match_ref(seed):
    """Pallas list-tile round scan / claim-max == jnp oracles, including
    on shapes that force internal row padding."""
    from repro.core.clustering import visit_order
    from repro.kernels.cluster.ops import (topk_cluster_assign,
                                           topk_cluster_round_scan)
    from repro.kernels.cluster.ref import (topk_claim_max_ref,
                                           topk_round_scan_ref)
    rng = np.random.default_rng(seed)
    sim, table = _instance(seed, S=21)               # 21 % 8 != 0: pads
    S = table.num_slots
    tk = topk_from_dense(sim, table, 5)
    alpha = jnp.float32(0.3)
    _, rank = visit_order(table)
    potential = np.asarray(table.valid)
    unresolved = jnp.asarray(potential & (rng.uniform(0, 1, S) > 0.4))
    is_rep = jnp.asarray(potential & (rng.uniform(0, 1, S) > 0.6)
                         & ~np.asarray(unresolved))

    blk, clm = topk_cluster_round_scan(tk.ids, tk.sims, rank, unresolved,
                                       is_rep, alpha)
    blk_r, clm_r = topk_round_scan_ref(tk.ids, tk.sims, rank, unresolved,
                                       is_rep, alpha)
    assert np.array_equal(np.asarray(blk), np.asarray(blk_r))
    assert np.array_equal(np.asarray(clm), np.asarray(clm_r))

    w, slot = topk_cluster_assign(tk.ids, tk.sims, rank, is_rep,
                                  table.valid, alpha)
    w_r, slot_r = topk_claim_max_ref(tk.ids, tk.sims, rank, is_rep,
                                     table.valid, alpha)
    assert np.array_equal(np.asarray(w), np.asarray(w_r))
    assert np.array_equal(np.asarray(slot), np.asarray(slot_r))


# ---------------------------------------------------------------------------
# Panel streaming: construction parity
# ---------------------------------------------------------------------------


def _pipeline_pieces(seed=3):
    from repro.core import similarity, voting
    from repro.core.segmentation import tsa2
    from repro.data.synthetic import ais_like
    from repro.kernels.stjoin.ops import subtrajectory_join
    batch, _ = ais_like(n_vessels=8, max_points=24, seed=seed)
    eps_sp, eps_t, delta_t, maxS, w, tau = 3.0, 600.0, 0.0, 4, 4, 0.2
    join, _ = subtrajectory_join(batch, batch, eps_sp, eps_t, delta_t)
    vote = voting.point_voting(join)
    masks = voting.neighbor_mask_packed(join)
    seg = tsa2(masks, batch.valid, w, tau, maxS)
    table = similarity.build_subtraj_table(batch, seg, vote, maxS)
    return batch, join, seg, table, maxS, (eps_sp, eps_t, delta_t)


@pytest.mark.parametrize("panel", [4, 8, 32, None])
def test_panel_stream_equals_dense_reduction(panel):
    """``similarity_topk`` (scatter per panel, both orientations) is
    bit-identical to reducing the dense ``similarity_matrix`` — lists,
    spill, degree, and moments — for every panel height."""
    from repro.core import similarity
    batch, join, seg, table, maxS, _ = _pipeline_pieces()
    dense = similarity.similarity_matrix(join, seg, seg.sub_local, table,
                                         maxS)
    want = topk_from_dense(dense, table, 8)
    got = similarity_topk(join, seg, seg.sub_local, table, maxS, k=8,
                          panel=panel)
    for f in ("ids", "sims", "spill", "degree", "row_sum", "row_sumsq"):
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), (panel, f)


def test_fused_panel_kernel_orientations_bitwise():
    """The panel-emitting fused kernel's (fwd, rev) slabs equal the dense
    fused raw accumulator's rows and transposed rows bit for bit."""
    from repro.kernels.stjoin.ops import (stjoin_sim_fused,
                                          stjoin_sim_panel_fused)
    batch, _, seg, table, maxS, (eps_sp, eps_t, dt) = _pipeline_pieces()
    S = table.num_slots
    kw = dict(rows=2, bc=4, bm=8)
    raw = np.asarray(stjoin_sim_fused(
        batch, batch, seg.sub_local, seg.sub_local, maxS, eps_sp, eps_t,
        dt, **kw))
    Sb = 8
    for p in range(S // Sb):
        fwd, rev = stjoin_sim_panel_fused(
            batch, batch, seg.sub_local, seg.sub_local, maxS, eps_sp,
            eps_t, dt, p0=p * Sb, panel=Sb, **kw)
        assert np.array_equal(np.asarray(fwd), raw[p * Sb:(p + 1) * Sb])
        assert np.array_equal(np.asarray(rev), raw.T[p * Sb:(p + 1) * Sb])


# ---------------------------------------------------------------------------
# Ring-merge algebra (DESIGN.md §12)
# ---------------------------------------------------------------------------


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_topk_merge_split_and_order_invariant(seed):
    """The ring fold's algebra: for distinct-id candidate lists the
    canonical merge is a set function of the (id, sim) pairs — any
    contiguous column-block split, any block permutation, and any
    pairwise merge grouping (left fold or random binary tree) yield
    bit-identical [S, K] id/sim lists *and* spill certificate.  This is
    the property that makes the ring similarity sweep's running
    one-block-at-a-time fold exact against the barrier k-way merge."""
    from repro.core.similarity import (merge_topk_blocks, merge_topk_lists,
                                       sort_topk_lists)
    rng = np.random.default_rng(seed)
    S, N, K = 7, 40, 5
    kk = K + 1
    # distinct ids per row (a permutation), sims nonnegative with zeros —
    # the (id=-1, sim=0) masking edge and the spill tail are both hit
    ids = jnp.asarray(np.stack([rng.permutation(N) for _ in range(S)])
                      .astype(np.int32))
    sims = np.asarray(rng.uniform(0, 1, (S, N)), np.float32)
    sims = jnp.asarray(sims * (rng.uniform(0, 1, (S, N)) > 0.3))
    ref = merge_topk_blocks(ids, sims, K)

    def check(got, ctx):
        for name, a, b in zip(("ids", "sims", "spill"), got, ref):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                seed, ctx, name)

    for trial in range(3):
        ncuts = int(rng.integers(1, 6))
        cuts = np.sort(rng.choice(np.arange(1, N), ncuts, replace=False))
        # each block pre-truncated to its own canonical top-(K+1) — what
        # every rank ships on the ring (selection containment keeps the
        # global top-(K+1), hence the spill, exact)
        blocks = [sort_topk_lists(ids[:, a:b], sims[:, a:b], kk)
                  for a, b in zip([0, *cuts], [*cuts, N])]
        order = [int(j) for j in rng.permutation(len(blocks))]

        ci = jnp.concatenate([blocks[j][0] for j in order], axis=1)
        cs = jnp.concatenate([blocks[j][1] for j in order], axis=1)
        check(merge_topk_blocks(ci, cs, K), f"concat trial={trial}")

        fi, fs = blocks[order[0]]
        for j in order[1:]:
            fi, fs = merge_topk_lists(fi, fs, *blocks[j], kk)
        check(merge_topk_blocks(fi, fs, K), f"fold trial={trial}")

        work = [blocks[j] for j in order]
        while len(work) > 1:
            i = int(rng.integers(0, len(work) - 1))
            a, b = work.pop(i), work.pop(i)
            work.insert(i, merge_topk_lists(a[0], a[1], b[0], b[1], kk))
        check(merge_topk_blocks(*work[0], K), f"tree trial={trial}")


# ---------------------------------------------------------------------------
# End-to-end pipeline parity
# ---------------------------------------------------------------------------


def test_run_dsc_topk_bit_identical(fig1, fig1_params):
    """sim_mode="topk" on both execution modes: bit-identical labels,
    SSCR, and RMSE; no dense matrix in the output; certified exact."""
    from repro.core.dsc import run_dsc
    batch, _ = fig1
    ref = run_dsc(batch, fig1_params)
    for kw in (dict(), dict(mode="fused"),
               dict(mode="fused", use_index=True),
               dict(cluster_engine="sequential"),
               dict(cluster_use_kernel=True)):
        out = run_dsc(batch, fig1_params, sim_mode="topk", **kw)
        assert out.sim is None and out.sim_topk is not None
        assert int(out.sim_overflow) == 0
        _assert_identical(ref.result, out.result, str(kw))
        assert float(out.sscr) == float(ref.sscr)
        assert float(out.rmse) == float(ref.rmse)


def test_run_dsc_topk_auto_widens_or_raises(fig1, fig1_params):
    """An undersized K either auto-widens to the certified fixed point
    (default) or raises loudly when retries are disabled."""
    from repro.core.dsc import run_dsc
    batch, _ = fig1
    ref = run_dsc(batch, fig1_params)
    out = run_dsc(batch, fig1_params, sim_mode="topk", sim_topk=2)
    assert int(out.sim_overflow) == 0
    assert out.sim_topk.k > 2                        # widened
    _assert_identical(ref.result, out.result)
    with pytest.raises(RuntimeError, match="sim_topk"):
        run_dsc(batch, fig1_params, sim_mode="topk", sim_topk=2,
                sim_topk_retry=False)
