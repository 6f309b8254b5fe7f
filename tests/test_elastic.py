"""Elastic recovery on the 8-device mesh (DESIGN.md §11).

Two halves, same subprocess pattern as ``test_resilient_dist.py``:

* an in-process driver that kills a P=8 checkpointed run at every stage
  boundary and resumes it on P∈{8,4,2,1} meshes (``elastic_resume``),
  asserting bit-identity against straight-through runs at the *new* P —
  plus the straggler-driven ``RebalancePolicy(mode="apply")`` path
  against its own oracle (a straight-through run partitioned at the
  applied cut from the start);
* a launcher matrix asserting the CLI exit codes: injected crash at
  P=8, elastic resume at P=4 → ok, cross-P resume *without* the flag →
  a plain error.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

pytestmark = [pytest.mark.distributed, pytest.mark.slow,
              pytest.mark.faults]

_STAGES = ("join", "segment", "similarity", "cluster", "refine")
_RESUME_PS = (4, 2, 1)

_DRIVER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import shutil
    import tempfile
    import numpy as np
    import jax
    from repro.data.synthetic import figure1_scenario
    from repro.core.types import DSCParams
    from repro.core.partitioning import partition_batch, repartition_batch
    from repro.core.plan import EnginePlan
    from repro.run import (FaultPlan, InjectedCrash, RebalancePolicy,
                           read_telemetry, run_resilient_distributed)
    from repro.run.resilient import STAGES

    batch, _ = figure1_scenario(n_per_route=4, points_per_leg=24, seed=0)
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=0.0, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    tmp = tempfile.mkdtemp()
    report = {}

    def mesh_for(P):
        return jax.make_mesh((P, 1), ("part", "model"))

    def sig(res):
        o = res.output
        return (np.asarray(o.result.member_of),
                np.asarray(o.result.is_rep),
                np.asarray(o.result.is_outlier),
                float(res.sscr), float(res.rmse))

    def same(a, b):
        return bool(all(np.array_equal(x, y) for x, y in zip(a, b)))

    # straight-through oracles at every target P
    oracle = {P: sig(run_resilient_distributed(
                  partition_batch(batch, P), params, mesh_for(P)))
              for P in (8, 4, 2, 1)}

    # kill at every stage boundary at P=8; resume elastically at the
    # smaller meshes (and once on the writing mesh: adaptation no-ops)
    for stage in STAGES:
        targets = (8, 4, 2, 1) if stage == "cluster" else (4, 2, 1)
        for newP in targets:
            root = f"{tmp}/el_{stage}_{newP}"
            try:
                run_resilient_distributed(
                    partition_batch(batch, 8), params, mesh_for(8),
                    checkpoint_dir=root,
                    fault_plan=FaultPlan(crash_at=stage))
                report[f"crash_{stage}_raised"] = False
            except InjectedCrash:
                report[f"crash_{stage}_raised"] = True
            res = run_resilient_distributed(
                partition_batch(batch, newP), params, mesh_for(newP),
                checkpoint_dir=root, elastic_resume=True)
            report[f"elastic_{stage}_{newP}_agree"] = same(
                sig(res), oracle[newP])
            report[f"elastic_{stage}_{newP}_from"] = res.resumed_from

    # cross-P resume WITHOUT the flag must refuse loudly
    root = f"{tmp}/noflag"
    try:
        run_resilient_distributed(
            partition_batch(batch, 8), params, mesh_for(8),
            checkpoint_dir=root, fault_plan=FaultPlan(crash_at="cluster"))
    except InjectedCrash:
        pass
    try:
        run_resilient_distributed(partition_batch(batch, 4), params,
                                  mesh_for(4), checkpoint_dir=root)
        report["noflag_error"] = None
    except ValueError as e:
        report["noflag_error"] = str(e)

    # rebalance apply: scripted slowdown on partition 1 triggers the
    # re-cut after join; oracle = straight-through at the applied cut
    rbroot = f"{tmp}/rb"
    parts4 = partition_batch(batch, 4)
    slow = FaultPlan(slow=(("join", 1, 30.0),))
    res_rb = run_resilient_distributed(
        parts4, params, mesh_for(4), checkpoint_dir=rbroot,
        fault_plan=slow, rebalance=RebalancePolicy(mode="apply"))
    rb_events = [e for e in read_telemetry(rbroot + "/telemetry.jsonl")
                 if e["event"] == "rebalanced"]
    report["rebalanced_events"] = len(rb_events)
    report["rebalance_count"] = res_rb.rebalance_count
    report["rebalanced_stage"] = (rb_events[0]["stage"] if rb_events
                                  else None)
    if rb_events:
        edges = np.asarray(rb_events[0]["edges"], np.float64)
        report["rebalanced_edge_count"] = int(edges.shape[0])
        res_or = run_resilient_distributed(
            repartition_batch(parts4, edges), params, mesh_for(4))
        report["rebalance_agree"] = same(sig(res_rb), sig(res_or))

    # crash after the applied rebalance: a plain (non-elastic) resume
    # adopts the checkpoint's edges and stays bit-identical
    rb2 = f"{tmp}/rb2"
    try:
        run_resilient_distributed(
            partition_batch(batch, 4), params, mesh_for(4),
            checkpoint_dir=rb2, rebalance=RebalancePolicy(mode="apply"),
            fault_plan=slow.replace(crash_at="cluster"))
    except InjectedCrash:
        pass
    res_ad = run_resilient_distributed(
        partition_batch(batch, 4), params, mesh_for(4),
        checkpoint_dir=rb2)
    ad_events = [e for e in read_telemetry(rb2 + "/telemetry.jsonl")
                 if e["event"] == "elastic_adopt_edges"]
    report["adopt_events"] = len(ad_events)
    report["adopt_agree"] = same(sig(res_ad), sig(res_rb))

    # ring comm schedules under kill-and-resume: a ring-plan run crashed
    # at the similarity boundary on a (4, 2) mesh resumes elastically on
    # (2, 2) — the ring similarity exchange reruns on the new mesh — and
    # stays bit-identical to the barrier plan's straight-through run;
    # telemetry events carry the active comm schedule
    barrier = EnginePlan(sim_mode="topk", sim_topk=48)
    ring = barrier.replace(halo_stream="ring", sim_exchange="ring")
    mesh42 = jax.make_mesh((4, 2), ("part", "model"))
    mesh22 = jax.make_mesh((2, 2), ("part", "model"))
    oracle_ring = sig(run_resilient_distributed(
        partition_batch(batch, 2), params, mesh22, plan=barrier))
    ringroot = f"{tmp}/ring"
    try:
        run_resilient_distributed(
            partition_batch(batch, 4), params, mesh42,
            checkpoint_dir=ringroot,
            fault_plan=FaultPlan(crash_at="similarity"), plan=ring)
    except InjectedCrash:
        pass
    res_ring = run_resilient_distributed(
        partition_batch(batch, 2), params, mesh22,
        checkpoint_dir=ringroot, elastic_resume=True, plan=ring)
    report["ring_elastic_agree"] = same(sig(res_ring), oracle_ring)
    report["ring_elastic_from"] = res_ring.resumed_from
    done = [e for e in read_telemetry(ringroot + "/telemetry.jsonl")
            if e["event"] == "stage_done"]
    report["ring_telemetry_comm"] = done[0].get("comm") if done else None

    # rebalance mode="off" emits neither suggestions nor applications
    res_off = run_resilient_distributed(
        parts4, params, mesh_for(4), fault_plan=slow,
        rebalance=RebalancePolicy(mode="off"))
    report["off_suggestions"] = sum(
        e["event"] in ("rebalance_suggestion", "rebalanced")
        for e in res_off.events)

    print("JSON" + json.dumps(report))
""")


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.parametrize("stage", _STAGES)
@pytest.mark.parametrize("newP", _RESUME_PS)
def test_elastic_resume_bit_identity(report, stage, newP):
    """P=8 checkpoint, killed at ``stage``, resumed on a P=``newP``
    mesh: bit-identical labels/SSCR/RMSE to straight-through at newP."""
    assert report[f"crash_{stage}_raised"]
    assert report[f"elastic_{stage}_{newP}_agree"]
    # join/segment state adapts in place; later stages rewind to the
    # segment boundary (their state is partition-bound)
    expect = min(_STAGES.index(stage), 2)
    assert report[f"elastic_{stage}_{newP}_from"] == expect


def test_elastic_resume_same_mesh_is_noop(report):
    assert report["elastic_cluster_8_agree"]
    assert report["elastic_cluster_8_from"] == _STAGES.index("cluster")


def test_cross_p_resume_without_flag_refuses(report):
    assert report["noflag_error"] is not None
    assert "elastic_resume" in report["noflag_error"]


def test_rebalance_apply_matches_oracle_cut(report):
    assert report["rebalanced_events"] == 1
    assert report["rebalance_count"] == 1
    assert report["rebalanced_stage"] == "join"
    assert report["rebalanced_edge_count"] == 5     # P+1 edges
    assert report["rebalance_agree"]


def test_resume_after_rebalance_adopts_edges(report):
    assert report["adopt_events"] == 1
    assert report["adopt_agree"]


def test_ring_elastic_resume_bit_identity(report):
    """Ring comm schedules survive kill-and-resume across meshes: the
    (4, 2) ring-plan checkpoint resumed on (2, 2) reruns the ring
    similarity exchange and matches the barrier twin bit for bit, and
    telemetry is tagged with the active comm schedule."""
    assert report["ring_elastic_from"] == _STAGES.index("similarity")
    assert report["ring_elastic_agree"]
    assert report["ring_telemetry_comm"] == {"halo_stream": "ring",
                                             "sim_exchange": "ring"}


def test_rebalance_off_is_silent(report):
    assert report["off_suggestions"] == 0


# ------------------------------------------------- launcher exit codes


@pytest.fixture(scope="module")
def launcher_codes(tmp_path_factory):
    from repro.run import FaultPlan
    from repro.run.resilient import EXIT_CODES
    tmp = tmp_path_factory.mktemp("elastic_cli")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    def run(extra):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.run_dsc",
             "--n-trajs", "24"] + extra,
            env=env, capture_output=True, text=True, timeout=900)
        return proc.returncode, proc.stderr

    crash = tmp / "crash.json"
    FaultPlan(crash_at="cluster").save(crash)
    ckpt = str(tmp / "ckpt")
    codes = {}
    codes["crash8"] = run(["--distributed", "8", "--resume-dir", ckpt,
                           "--fault-plan", str(crash)])
    codes["noflag4"] = run(["--distributed", "4", "--resume-dir", ckpt])
    codes["elastic4"] = run(["--distributed", "4", "--resume-dir", ckpt,
                             "--elastic-resume"])
    codes["elastic_alone"] = run(["--elastic-resume"])
    codes["expected"] = EXIT_CODES
    return codes


def test_launcher_elastic_exit_codes(launcher_codes):
    c, exit_codes = launcher_codes, launcher_codes["expected"]
    assert c["crash8"][0] == exit_codes["injected_crash"]
    # cross-P without the flag: refused (unclassified error), told how
    assert c["noflag4"][0] not in (0, exit_codes["injected_crash"])
    assert "elastic" in c["noflag4"][1]
    assert c["elastic4"][0] == exit_codes["ok"]
    # --elastic-resume without --resume-dir/--distributed: usage error
    assert c["elastic_alone"][0] == 2
