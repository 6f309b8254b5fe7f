"""End-to-end behaviour tests: the paper's own validation scenario (Sec. 6.2).

The Fig. 1 synthetic has known ground truth: subtrajectory clusters per
(origin/destination leg).  DSC must recover the leg structure with perfect
cluster purity; a whole-trajectory method (T-OPTICS) can only see the six
routes.  This mirrors the paper's "Accuracy = 100%, F-measure = 1" check.
"""
import numpy as np
import pytest

from repro.core.dsc import cluster_summary, run_dsc
from repro.core.evaluation import cluster_purity, leg_labels, pairwise_f1
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams
from repro.data.synthetic import figure1_scenario, route_origins_dests


def _truth(batch, route, out, max_subs):
    origins, dests = route_origins_dests(route)
    sub_local = np.asarray(out.seg.sub_local)
    t = np.asarray(batch.t)
    v = np.asarray(batch.valid)
    t_split = float(t[v].max()) / 2
    return leg_labels(batch, sub_local, origins, dests, t_split, max_subs)


@pytest.fixture(scope="module")
def dsc_out(fig1, fig1_params):
    batch, labels = fig1
    return run_dsc(batch, fig1_params)


def _assignments(out):
    member_of = np.asarray(out.result.member_of)
    is_rep = np.asarray(out.result.is_rep)
    valid = np.asarray(out.table.valid)
    assign = {}
    for s in np.nonzero(valid)[0]:
        if is_rep[s]:
            assign[int(s)] = int(s)
        elif member_of[s] >= 0:
            assign[int(s)] = int(member_of[s])
    return assign


def test_groundtruth_recovery(fig1, fig1_params, dsc_out):
    """Near-perfect purity of clusters w.r.t. the leg ground truth (TSA2)."""
    batch, route = fig1
    out = dsc_out
    assign = _assignments(out)
    assert len(assign) > 0
    truth = _truth(batch, route, out, fig1_params.max_subtrajs_per_traj)
    purity = cluster_purity(assign, truth)
    assert purity >= 0.95, f"purity {purity}"
    f1 = pairwise_f1(assign, truth)
    assert f1 >= 0.5, f"pairwise F1 {f1}"


def test_outliers_are_the_unshared_legs(fig1, fig1_params, dsc_out):
    """O->A and O->B legs (4 supporters each) fall below the voting
    threshold and are isolated — the Fig. 1(b) structure."""
    batch, route = fig1
    out = dsc_out
    outliers = np.nonzero(np.asarray(out.result.is_outlier))[0]
    truth = _truth(batch, route, out, fig1_params.max_subtrajs_per_traj)
    # outliers should be dominated by the low-support destination legs
    # O->A and O->B (Fig. 1(b))
    tails = [truth[s] for s in outliers if s in truth]
    assert tails, "expected some outliers"
    frac = np.mean([t in [("D", "A"), ("D", "B")] for t in tails])
    assert frac >= 0.9, f"outlier composition {tails}"


def test_sscr_positive_and_rmse_bounded(dsc_out, fig1_params):
    assert float(dsc_out.sscr) > 0.0
    # Lemma 1: member mean distance <= eps_sp * (1 - alpha); the RMSE proxy
    # is bounded by eps_sp
    assert float(dsc_out.rmse) <= fig1_params.eps_sp


def test_tsa1_finds_flock_through_O(fig1):
    """TSA1 (density) merges across O (Example 2's contrast with TSA2)."""
    batch, route = fig1
    params = DSCParams(eps_sp=0.42, eps_t=1.0, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa1")
    out = run_dsc(batch, params)
    s = cluster_summary(out)
    assert s["num_clusters"] >= 1
    out2 = run_dsc(batch, params.replace(segmentation="tsa2"))
    s2 = cluster_summary(out2)
    assert s["num_clusters"] <= s2["num_clusters"]


def test_toptics_sees_routes_not_legs(fig1):
    from repro.core.baselines.toptics import t_optics
    batch, route = fig1
    res = t_optics(batch, eps=2.0, min_pts=3, xi_eps=0.2)
    labels = res["labels"]
    assert (labels >= 0).any()
    for c in set(labels) - {-1}:
        rs = set(route[np.nonzero(labels == c)[0]])
        assert len(rs) == 1


def test_figure1_outlier_variant():
    """Low-support tails (O->A / O->B) become outliers."""
    batch, route = figure1_scenario(n_per_route=2, points_per_leg=24, seed=3)
    params = DSCParams(eps_sp=0.42, eps_t=1.0, w=6, tau=0.2,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    out = run_dsc(batch, params)
    assert int(np.asarray(out.result.is_outlier).sum()) >= 2


def test_kernel_path_matches_reference(fig1, fig1_params):
    """The Pallas stjoin-backed pipeline reproduces the reference output."""
    batch, _ = fig1
    a = run_dsc(batch, fig1_params, plan=EnginePlan(use_kernel=False))
    b = run_dsc(batch, fig1_params, plan=EnginePlan(use_kernel=True))
    np.testing.assert_allclose(np.asarray(a.vote), np.asarray(b.vote),
                               atol=1e-4)
    assert (np.asarray(a.result.member_of) ==
            np.asarray(b.result.member_of)).mean() > 0.99


@pytest.mark.parametrize("delta_t", [0.0, 2.0])
def test_kernel_index_plan_runs_recorded_pruned_programs(fig1, fig1_params,
                                                         delta_t):
    """The kernel + index plan runs the pruned join and one tail program,
    whose similarity sweep is pruned by its own tile plan: the same
    similarity and labels as the dense kernel plan, with the delta_t
    Refine off and on."""
    from repro.utils.programs import record_programs
    batch, _ = fig1
    params = fig1_params.replace(delta_t=delta_t)
    with record_programs() as programs:
        got = run_dsc(batch, params,
                      plan=EnginePlan(use_kernel=True, use_index=True))
    assert [name for name, _ in programs] == ["_join_sweep",
                                              "_run_dsc_from_join"]
    want = run_dsc(batch, params, plan=EnginePlan(use_kernel=True))
    np.testing.assert_array_equal(np.asarray(got.sim), np.asarray(want.sim))
    for f in ("member_of", "is_rep", "is_outlier"):
        np.testing.assert_array_equal(np.asarray(getattr(got.result, f)),
                                      np.asarray(getattr(want.result, f)))
