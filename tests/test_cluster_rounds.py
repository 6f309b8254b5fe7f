"""Round-parallel clustering engine: label identity with the sequential
oracle (hypothesis-driven), kernel parity, and the degenerate extremes.

The engine contract (DESIGN.md §6) is *bit identity*: ``member_of``,
``member_sim``, ``is_rep`` and ``is_outlier`` must equal the sequential
Algorithm 4 transcription exactly — including argsort tie-break
determinism under tied voting values — on any similarity matrix.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.clustering import (cluster, cluster_rounds,
                                   cluster_sequential, visit_order)
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams, SubtrajTable
from repro.kernels.cluster.ops import (cluster_assign, cluster_round_scan,
                                       plan_tiles)
from repro.kernels.cluster.ref import claim_max_ref, round_scan_ref

FIELDS = ("member_of", "member_sim", "is_rep", "is_outlier")

PARAM_GRID = (
    DSCParams(alpha_sigma=0.0, k_sigma=0.0),
    DSCParams(alpha_sigma=0.5, k_sigma=-0.5),
    DSCParams(alpha_abs=0.2, k_abs=1.0),
    DSCParams(alpha_abs=0.0, k_abs=0.0),
)


def _instance(seed, S=24, tied_voting=False, symmetric=True):
    rng = np.random.default_rng(seed)
    raw = rng.uniform(0, 1, (S, S)).astype(np.float32)
    sim = raw * (rng.uniform(0, 1, (S, S)) > 0.5)
    if symmetric:
        sim = np.maximum(sim, sim.T)
    np.fill_diagonal(sim, 0.0)
    valid = rng.uniform(0, 1, S) > 0.1
    # tied voting: draw from a 3-value set so most slots collide and the
    # stable-argsort (slot-index) tie break decides the visit order
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
def test_rounds_match_sequential(seed):
    sim, table = _instance(seed)
    for params in PARAM_GRID:
        seq = cluster_sequential(sim, table, params)
        rp, rounds = cluster_rounds(sim, table, params, with_rounds=True)
        _assert_identical(seq, rp, f"seed={seed}")
        assert int(rounds) <= table.num_slots


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_rounds_match_sequential_tied_voting(seed):
    """Voting drawn from {0, 1, 2}: ties everywhere — the visit order (and
    therefore every claim) hinges on stable-argsort determinism."""
    sim, table = _instance(seed, tied_voting=True)
    for params in PARAM_GRID:
        _assert_identical(cluster_sequential(sim, table, params),
                          cluster_rounds(sim, table, params),
                          f"seed={seed}")


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_rounds_match_sequential_asymmetric(seed):
    """The engine must not assume a symmetrized matrix: claims always read
    the claiming representative's row, in either engine."""
    sim, table = _instance(seed, symmetric=False)
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    _assert_identical(cluster_sequential(sim, table, params),
                      cluster_rounds(sim, table, params), f"seed={seed}")


def test_all_outlier_extreme():
    """No similarity and an unreachable k: every valid slot is an outlier,
    resolved in zero rounds (no potential representatives)."""
    S = 16
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.ones(S), card=jnp.ones(S, jnp.int32),
        valid=jnp.ones(S, bool), traj_row=jnp.arange(S, dtype=jnp.int32))
    params = DSCParams(alpha_abs=0.5, k_abs=100.0)
    sim = jnp.zeros((S, S))
    seq = cluster_sequential(sim, table, params)
    rp, rounds = cluster_rounds(sim, table, params, with_rounds=True)
    _assert_identical(seq, rp)
    assert bool(np.asarray(rp.is_outlier).all())
    assert int(rounds) == 0


def test_all_one_cluster_extreme():
    """Uniform high similarity, k=0: the first-visited slot claims every
    other slot; the round engine needs exactly 2 rounds however large S."""
    S = 32
    sim = np.full((S, S), 0.9, np.float32)
    np.fill_diagonal(sim, 0.0)
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.ones(S), card=jnp.ones(S, jnp.int32),
        valid=jnp.ones(S, bool), traj_row=jnp.arange(S, dtype=jnp.int32))
    params = DSCParams(alpha_abs=0.5, k_abs=0.0)
    seq = cluster_sequential(jnp.asarray(sim), table, params)
    rp, rounds = cluster_rounds(jnp.asarray(sim), table, params,
                                with_rounds=True)
    _assert_identical(seq, rp)
    assert int(np.asarray(rp.is_rep).sum()) == 1
    assert int(rounds) == 2


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_kernel_engine_matches_sequential(seed):
    """use_kernel=True (Pallas round scan + claim-max, padded tiles) is
    bit-identical to the oracle."""
    sim, table = _instance(seed, tied_voting=(seed % 2 == 0))
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    _assert_identical(cluster_sequential(sim, table, params),
                      cluster_rounds(sim, table, params, use_kernel=True),
                      f"seed={seed}")


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_kernel_primitives_match_ref(seed):
    """The tiled round scan / claim-max equal the jnp oracle on padded
    operands with mid-convergence round state."""
    rng = np.random.default_rng(seed)
    sim, table = _instance(seed, S=40)       # S=40: forces internal padding
    S = table.num_slots
    assert plan_tiles(S)[2] > S              # wrappers must pad this shape
    alpha = jnp.float32(0.3)
    order, rank = visit_order(table)

    potential = np.asarray(table.valid)
    unresolved = jnp.asarray(potential & (rng.uniform(0, 1, S) > 0.4))
    is_rep = jnp.asarray(potential & (rng.uniform(0, 1, S) > 0.6)
                         & ~np.asarray(unresolved))

    blk, clm = cluster_round_scan(sim, rank, unresolved, is_rep, alpha)
    blk_r, clm_r = round_scan_ref(sim, rank, unresolved, is_rep, alpha)
    assert np.array_equal(np.asarray(blk), np.asarray(blk_r))
    assert np.array_equal(np.asarray(clm), np.asarray(clm_r))

    w, slot = cluster_assign(sim, rank, is_rep, table.valid, alpha)
    w_r, slot_r = claim_max_ref(sim, order, rank, is_rep, table.valid,
                                alpha)
    assert np.array_equal(np.asarray(w), np.asarray(w_r))
    assert np.array_equal(np.asarray(slot), np.asarray(slot_r))


def test_fixed_trip_fallback_matches_while():
    """max_rounds=S (fori_loop fallback) equals the while_loop engine —
    converged rounds are no-ops; max_rounds < S is rejected (it could
    silently return partial labels)."""
    sim, table = _instance(7)
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    _assert_identical(
        cluster_rounds(sim, table, params, max_rounds=table.num_slots),
        cluster_rounds(sim, table, params))
    with pytest.raises(ValueError):
        cluster_rounds(sim, table, params, max_rounds=table.num_slots - 1)


def test_voting_threshold_large_mean_small_std():
    """k from sigma-relative voting stats must not collapse under
    mean >> std (centered variance, not the E[x^2]-E[x]^2 identity)."""
    S = 16
    rng = np.random.default_rng(0)
    voting = (10000.0 + rng.uniform(-0.005, 0.005, S)).astype(np.float32)
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.asarray(voting), card=jnp.ones(S, jnp.int32),
        valid=jnp.ones(S, bool), traj_row=jnp.arange(S, dtype=jnp.int32))
    from repro.core.clustering import resolve_thresholds
    params = DSCParams(alpha_sigma=0.0, k_sigma=1.0)
    _, k = resolve_thresholds(params, jnp.zeros((S, S)), table)
    v64 = voting.astype(np.float64)
    want = v64.mean() + v64.std()
    assert abs(float(k) - want) < 1e-3, (float(k), want)


def test_dispatcher_engines():
    sim, table = _instance(11)
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)
    _assert_identical(cluster(sim, table, params, engine="sequential"),
                      cluster(sim, table, params, engine="rounds"))
    with pytest.raises(ValueError):
        cluster(sim, table, params, engine="bogus")


def test_engine_parity_through_pipeline(fig1, fig1_params):
    """run_dsc with plan.cluster_engine="rounds" (default) equals the
    sequential-engine run end to end, single host."""
    from repro.core.dsc import run_dsc
    batch, _ = fig1
    out_r = run_dsc(batch, fig1_params)
    out_s = run_dsc(batch, fig1_params,
                    plan=EnginePlan(cluster_engine="sequential"))
    _assert_identical(out_r.result, out_s.result)
    assert float(out_r.sscr) == float(out_s.sscr)


def test_kernel_cluster_through_pipeline():
    """run_dsc with plan.cluster_use_kernel — the production entry to the
    Pallas cluster kernels — matches the jnp engine end to end (small
    instance: interpret mode pays per program instance)."""
    from repro.core.dsc import run_dsc
    from repro.data.synthetic import ais_like
    batch, _ = ais_like(n_vessels=8, max_points=24, seed=3)
    params = DSCParams(eps_sp=3.0, eps_t=600.0, w=4, tau=0.2,
                       alpha_sigma=0.0, k_sigma=0.0,
                       max_subtrajs_per_traj=4)
    out = run_dsc(batch, params)
    out_k = run_dsc(batch, params, plan=EnginePlan(cluster_use_kernel=True))
    _assert_identical(out.result, out_k.result)


@pytest.mark.slow
def test_engine_parity_distributed_single_device(fig1, fig1_params):
    """Distributed program (P=1 mesh on the single real device): the
    per-partition round engine matches the sequential engine exactly."""
    import jax
    from repro.core.distributed import run_dsc_distributed
    from repro.core.partitioning import partition_batch
    batch, _ = fig1
    mesh = jax.make_mesh((1, 1), ("part", "model"))
    parts = partition_batch(batch, 1)
    out_r = run_dsc_distributed(parts, fig1_params, mesh)
    out_s = run_dsc_distributed(parts, fig1_params, mesh,
                                plan=EnginePlan(cluster_engine="sequential"))
    _assert_identical(out_r.result, out_s.result)
