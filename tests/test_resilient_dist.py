"""Resilient runner on the 8-device distributed pipeline.

Same subprocess pattern as ``test_distributed.py``: one driver under
``--xla_force_host_platform_device_count=8`` exercises crash/resume at
every stage boundary, the overflow policies, and the straggler
telemetry, and prints a JSON report the tests assert on.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_DRIVER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import tempfile
    import numpy as np
    import jax
    from repro.data.synthetic import figure1_scenario
    from repro.core.types import DSCParams
    from repro.core.partitioning import partition_batch
    from repro.core.distributed import run_dsc_distributed
    from repro.core.plan import EnginePlan
    from repro.run import FaultPlan, InjectedCrash, run_resilient_distributed
    from repro.run.resilient import STAGES

    batch, _ = figure1_scenario(n_per_route=4, points_per_leg=24, seed=0)
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=0.0, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    mesh = jax.make_mesh((4, 2), ("part", "model"))
    parts = partition_batch(batch, 4)
    tmp = tempfile.mkdtemp()
    report = {}

    # monolithic dense run = the bit-identity reference
    ref = run_dsc_distributed(parts, params, mesh)
    rm = np.asarray(ref.result.member_of)
    rr = np.asarray(ref.result.is_rep)
    ro = np.asarray(ref.result.is_outlier)
    rs = np.asarray(ref.result.member_sim)

    def agrees(out):
        r = out.result
        return bool((np.asarray(r.member_of) == rm).all()
                    and (np.asarray(r.is_rep) == rr).all()
                    and (np.asarray(r.is_outlier) == ro).all()
                    and (np.asarray(r.member_sim) == rs).all())

    # fresh staged run (no persistence) reproduces the monolith
    res = run_resilient_distributed(parts, params, mesh)
    report["fresh_agree"] = agrees(res.output)

    # kill at every stage boundary; resume must be bit-identical
    for stage in STAGES:
        root = f"{tmp}/crash_{stage}"
        try:
            run_resilient_distributed(
                parts, params, mesh, checkpoint_dir=root,
                fault_plan=FaultPlan(crash_at=stage))
            report[f"crash_{stage}_raised"] = False
        except InjectedCrash:
            report[f"crash_{stage}_raised"] = True
        r2 = run_resilient_distributed(parts, params, mesh,
                                       checkpoint_dir=root)
        report[f"resume_{stage}_from"] = r2.resumed_from
        report[f"resume_{stage}_agree"] = agrees(r2.output)

    # stage-level widen from a spilling K recovers the dense labels
    topk4 = EnginePlan(sim_mode="topk", sim_topk=4)
    rw = run_resilient_distributed(parts, params, mesh, plan=topk4,
                                   on_overflow="widen")
    report["widen_count"] = rw.widen_count
    report["widen_agree"] = agrees(rw.output)
    report["widen_overflow"] = int(
        np.asarray(rw.output.sim_diag)[:, 3].sum())

    # degrade completes and records the nonzero certificate
    rd = run_resilient_distributed(parts, params, mesh, plan=topk4,
                                   on_overflow="degrade")
    report["degrade_overflow"] = int(
        np.asarray(rd.output.sim_diag)[:, 3].sum())

    # the monolithic driver's on_overflow="widen" completes too
    # (acceptance criterion: no raise, clean certificate, same labels)
    om = run_dsc_distributed(parts, params, mesh, plan=topk4,
                             on_overflow="widen")
    report["monolith_widen_agree"] = agrees(om)
    report["monolith_widen_overflow"] = int(
        np.asarray(om.sim_diag)[:, 3].sum())

    # scripted slowdown on partition 2: flag + rebalance suggestion
    slow = tuple((s, 2, 30.0) for s in STAGES)
    rsl = run_resilient_distributed(parts, params, mesh,
                                    fault_plan=FaultPlan(slow=slow))
    flags = [e for e in rsl.events if e["event"] == "straggler_flagged"]
    rebal = [e for e in rsl.events
             if e["event"] == "rebalance_suggestion"]
    report["straggler_flagged_p2"] = bool(
        flags and all("2" in e["partitions"] for e in flags))
    report["rebalance_edges"] = rebal[-1]["edges"] if rebal else None
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


pytestmark = [pytest.mark.distributed, pytest.mark.slow,
              pytest.mark.faults]

_STAGES = ("join", "segment", "similarity", "cluster", "refine")


def test_fresh_staged_run_matches_monolith(report):
    assert report["fresh_agree"]


@pytest.mark.parametrize("stage", _STAGES)
def test_resume_bit_identity(report, stage):
    assert report[f"crash_{stage}_raised"]
    assert report[f"resume_{stage}_from"] == _STAGES.index(stage)
    assert report[f"resume_{stage}_agree"]


def test_widen_recovers_dense_labels(report):
    assert report["widen_count"] >= 1
    assert report["widen_agree"]
    assert report["widen_overflow"] == 0


def test_degrade_records_certificate(report):
    assert report["degrade_overflow"] > 0


def test_monolith_widen_policy(report):
    assert report["monolith_widen_agree"]
    assert report["monolith_widen_overflow"] == 0


def test_straggler_flag_and_rebalance(report):
    assert report["straggler_flagged_p2"]
    edges = report["rebalance_edges"]
    assert edges is not None and len(edges) == 5
    assert edges[0] == -float("inf") and edges[-1] == float("inf")
