"""Distributed pipeline tests.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count`` so the main test session keeps a
single real device (required by the harness contract).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_DRIVER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.data.synthetic import figure1_scenario
    from repro.core.types import DSCParams
    from repro.core.partitioning import partition_batch
    from repro.core.distributed import run_dsc_distributed
    from repro.core.dsc import run_dsc
    from repro.core.plan import EnginePlan

    batch, labels = figure1_scenario(n_per_route=4, points_per_leg=24, seed=0)
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=0.0, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    report = {}

    # single-host reference
    ref = run_dsc(batch, params)
    report["ref_reps"] = int(np.asarray(ref.result.is_rep).sum())
    report["ref_outliers"] = int(np.asarray(ref.result.is_outlier).sum())

    # P=1 distributed == single host (same partition content)
    mesh1 = jax.make_mesh((1, 2), ("part", "model"))
    parts1 = partition_batch(batch, 1)
    out1 = run_dsc_distributed(parts1, params, mesh1)
    report["p1_member_agree"] = float(
        (np.asarray(out1.result.member_of)
         == np.asarray(ref.result.member_of)).mean())
    report["p1_rep_agree"] = float(
        (np.asarray(out1.result.is_rep)
         == np.asarray(ref.result.is_rep)).mean())

    # P=4 x model=2
    mesh = jax.make_mesh((4, 2), ("part", "model"))
    parts = partition_batch(batch, 4)
    out = run_dsc_distributed(parts, params, mesh)
    res, valid = out.result, np.asarray(out.table.valid)
    member_of = np.asarray(res.member_of)
    is_rep = np.asarray(res.is_rep)
    is_out = np.asarray(res.is_outlier)
    report["p4_reps"] = int(is_rep.sum())
    report["p4_outliers"] = int(is_out.sum())
    report["p4_members"] = int(((member_of >= 0) & ~is_rep).sum())
    # every member's target is a representative
    ok = True
    for s in np.nonzero(valid & (member_of >= 0) & ~is_rep)[0]:
        ok &= bool(is_rep[member_of[s]])
    report["p4_members_point_at_reps"] = bool(ok)
    # states partition valid slots
    seen = np.asarray(out.active).any(0)
    state = is_rep.astype(int) + ((member_of >= 0) & ~is_rep) + is_out
    report["p4_state_partition"] = bool((state[seen] == 1).all())

    # kernel-backed join agrees
    out_k = run_dsc_distributed(parts, params, mesh,
                                plan=EnginePlan(use_kernel=True))
    report["p4_kernel_agree"] = float(
        (np.asarray(out_k.result.member_of) == member_of).mean())

    # spatiotemporal-index-pruned join agrees exactly
    out_i = run_dsc_distributed(parts, params, mesh,
                                plan=EnginePlan(use_index=True))
    report["p4_index_agree"] = float(
        (np.asarray(out_i.result.member_of) == member_of).mean())

    # fused streaming mode: identical clusters with no per-rank join cube
    out_f = run_dsc_distributed(parts, params, mesh,
                                plan=EnginePlan(mode="fused"))
    report["p4_fused_agree"] = float(
        (np.asarray(out_f.result.member_of) == member_of).mean())
    report["p4_fused_vote_close"] = bool(np.allclose(
        np.asarray(out_f.vote), np.asarray(out.vote), atol=1e-4))

    # fused Pallas TSA2 segmentation kernel: bit-identical labels to the
    # jnp packed-word engine (tsa2 is the params' segmentation)
    out_sk = run_dsc_distributed(parts, params, mesh,
                                 plan=EnginePlan(seg_use_kernel=True))
    report["p4_seg_kernel_agree"] = bool(
        (np.asarray(out_sk.result.member_of) == member_of).all()
        and (np.asarray(out_sk.result.is_rep) == is_rep).all()
        and (np.asarray(out_sk.result.is_outlier) == is_out).all())

    # sequential clustering oracle: the round-parallel per-partition
    # engine (the default above) must be label-identical
    out_s = run_dsc_distributed(parts, params, mesh,
                                plan=EnginePlan(cluster_engine="sequential"))
    report["p4_cluster_engine_agree"] = bool(
        (np.asarray(out_s.result.member_of) == member_of).all()
        and (np.asarray(out_s.result.is_rep) == is_rep).all()
        and (np.asarray(out_s.result.is_outlier) == is_out).all())

    # sparse SP relation (sim_mode="topk"): per-rank column blocks +
    # transpose all_to_all + top-(K+1) allgather merge — bit-identical
    # global labels whenever the spill certificate is clean, in both
    # execution modes
    for key, kw in (("p4_topk", {}), ("p4_topk_fused", {"mode": "fused"})):
        out_t = run_dsc_distributed(
            parts, params, mesh,
            plan=EnginePlan(sim_mode="topk", sim_topk=48, **kw))
        report[key + "_overflow"] = int(
            np.asarray(out_t.sim_diag)[:, 3].sum())
        report[key + "_agree"] = bool(
            (np.asarray(out_t.result.member_of) == member_of).all()
            and (np.asarray(out_t.result.member_sim)
                 == np.asarray(res.member_sim)).all()
            and (np.asarray(out_t.result.is_rep) == is_rep).all()
            and (np.asarray(out_t.result.is_outlier) == is_out).all())

    # ring-pipelined collectives: the P-step ppermute schedules must be
    # bit-identical to their barrier twins on every label field, for the
    # dense and the top-K similarity paths, materializing and fused
    ring_cells = (
        ("p4_ring_dense", dict(halo_stream="ring"), out),
        ("p4_ring_dense_simring", dict(halo_stream="ring",
                                       sim_exchange="ring"), out),
        ("p4_ring_topk", dict(sim_mode="topk", sim_topk=48,
                              halo_stream="ring", sim_exchange="ring"),
         out),                 # barrier top-K == dense (asserted above)
        ("p4_ring_fused", dict(mode="fused", halo_stream="ring"), out_f),
    )
    for key, kw, twin in ring_cells:
        out_r = run_dsc_distributed(parts, params, mesh,
                                    plan=EnginePlan(**kw))
        report[key + "_agree"] = bool(all(
            (np.asarray(getattr(out_r.result, f))
             == np.asarray(getattr(twin.result, f))).all()
            for f in ("member_of", "member_sim", "is_rep", "is_outlier")))

    # the comm-schedule autotuner sweep: all four schedule candidates
    # must verify bit-identical against the barrier oracle, and the
    # winner must be a verified candidate
    from repro.tune.autotune import tune_comm
    tr = tune_comm(parts, params, mesh)
    report["comm_sweep_candidates"] = len(tr.candidates)
    report["comm_sweep_verified"] = sum(c.verified for c in tr.candidates)
    report["comm_winner_verified"] = bool(tr.winner.verified)

    print("JSON" + json.dumps(report))
""")


@pytest.fixture(scope="module")
def dist_report():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.distributed
@pytest.mark.slow
def test_p1_matches_single_host(dist_report):
    assert dist_report["p1_member_agree"] >= 0.999
    assert dist_report["p1_rep_agree"] >= 0.999


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_structure(dist_report):
    assert dist_report["p4_reps"] > 0
    assert dist_report["p4_members"] > 0
    assert dist_report["p4_members_point_at_reps"]
    assert dist_report["p4_state_partition"]


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_kernel_path(dist_report):
    assert dist_report["p4_kernel_agree"] >= 0.98


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_index_pruned_join_agrees(dist_report):
    """use_index=True (halo bbox buckets + pair pruning) is lossless."""
    assert dist_report["p4_index_agree"] == 1.0


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_fused_streaming_agrees(dist_report):
    """mode="fused" (no per-rank join cube) matches the materializing run."""
    assert dist_report["p4_fused_agree"] == 1.0
    assert dist_report["p4_fused_vote_close"]


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_seg_kernel_identical(dist_report):
    """seg_use_kernel=True (fused Pallas TSA2 Jaccard kernel in phase 3)
    is bit-identical to the jnp packed-word engine end to end."""
    assert dist_report["p4_seg_kernel_agree"]


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_cluster_engines_identical(dist_report):
    """Round-parallel vs sequential clustering engine, per partition +
    Algorithm 5 refinement: bit-identical global labels."""
    assert dist_report["p4_cluster_engine_agree"]


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_topk_sim_identical(dist_report):
    """sim_mode="topk" (sparse SP relation: [S, K+1] allgather instead of
    the dense [S, S] psum) is bit-identical to the dense runs in both
    execution modes, with a clean exactness certificate."""
    for key in ("p4_topk", "p4_topk_fused"):
        assert dist_report[key + "_overflow"] == 0
        assert dist_report[key + "_agree"]


@pytest.mark.distributed
@pytest.mark.slow
def test_p4_ring_schedules_identical(dist_report):
    """halo_stream="ring" / sim_exchange="ring" (P-step ppermute schedules,
    DESIGN.md §12) are bit-identical to their barrier twins on every label
    field — dense and top-K similarity, materializing and fused."""
    for key in ("p4_ring_dense", "p4_ring_dense_simring", "p4_ring_topk",
                "p4_ring_fused"):
        assert dist_report[key + "_agree"], key


@pytest.mark.distributed
@pytest.mark.slow
def test_comm_schedule_sweep_all_verified(dist_report):
    """tune_comm: every barrier/ring schedule candidate verifies
    bit-identical against the barrier oracle; the winner is verified."""
    assert dist_report["comm_sweep_candidates"] == 4
    assert dist_report["comm_sweep_verified"] == 4
    assert dist_report["comm_winner_verified"]


def test_partitioning_is_equi_depth():
    from repro.core.partitioning import partition_batch
    from repro.data.synthetic import ais_like
    batch, _ = ais_like(n_vessels=32, max_points=64, seed=5)
    parts = partition_batch(batch, 4)
    counts = np.asarray(parts.valid).sum(axis=(1, 2))
    total = counts.sum()
    assert total == int(np.asarray(batch.valid).sum())
    assert counts.min() >= 0.5 * total / 4, counts  # balanced within 2x
    # every point's time inside its partition range
    t = np.asarray(parts.t)
    v = np.asarray(parts.valid)
    rng = np.asarray(parts.ranges)
    for p in range(4):
        tp = t[p][v[p]]
        if len(tp):
            assert (tp >= rng[p, 0] - 1e-5).all()
            assert (tp <= rng[p, 1] + 1e-5).all()
