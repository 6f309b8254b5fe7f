"""Resilient stage runner + deterministic fault injection (DESIGN.md §10).

Everything here carries the ``faults`` marker (the chaos CI job runs
``-m faults``); the cheap in-process cases also run in tier-1.  The
launcher exit-code matrix spawns real subprocesses and is additionally
``slow``.  8-device coverage lives in ``test_resilient_dist.py``.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, load_checkpoint,
                              load_checkpoint_flat, save_checkpoint)
from repro.core.dsc import run_dsc
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams
from repro.data.synthetic import figure1_scenario
from repro.distributed.straggler import (StragglerMonitor,
                                         suggest_rebalance_edges)
from repro.run import (CheckpointCorruption, FaultInjector, FaultPlan,
                       InjectedCrash, RetriesExhausted, TransientFault,
                       retry_with_backoff, run_resilient)
from repro.run.resilient import EXIT_CODES, STAGES, OverflowViolation

pytestmark = pytest.mark.faults

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# a K small enough to overflow the certificate on the scenario
TOPK2 = EnginePlan(sim_mode="topk", sim_topk=2)


@pytest.fixture(scope="module")
def scenario():
    batch, _ = figure1_scenario(n_per_route=2, points_per_leg=16, seed=0)
    params = DSCParams(eps_sp=0.42, eps_t=1.0, delta_t=0.0, w=6, tau=0.15,
                       alpha_sigma=-1.0, k_sigma=-1.0, segmentation="tsa2")
    return batch, params


@pytest.fixture(scope="module")
def reference(scenario):
    batch, params = scenario
    return run_dsc(batch, params)


def assert_bit_identical(out, ref):
    r, q = out.result, ref.result
    assert (np.asarray(r.member_of) == np.asarray(q.member_of)).all()
    assert (np.asarray(r.is_rep) == np.asarray(q.is_rep)).all()
    assert (np.asarray(r.is_outlier) == np.asarray(q.is_outlier)).all()
    assert (np.asarray(r.member_sim) == np.asarray(q.member_sim)).all()
    assert float(out.sscr) == float(ref.sscr)
    assert float(out.rmse) == float(ref.rmse)


# ------------------------------------------------------------ stage graph


def test_fresh_run_matches_monolith(scenario, reference):
    batch, params = scenario
    res = run_resilient(batch, params)
    assert res.resumed_from == 0
    assert res.widen_count == 0
    assert res.fallback_steps == []
    assert_bit_identical(res.output, reference)


def test_checkpointed_run_writes_every_stage(scenario, reference, tmp_path):
    batch, params = scenario
    res = run_resilient(batch, params, checkpoint_dir=tmp_path / "ckpt")
    assert_bit_identical(res.output, reference)
    mgr = CheckpointManager(tmp_path / "ckpt")
    assert mgr.available_steps() == list(range(1, len(STAGES) + 1))
    # telemetry JSONL exists and replays the in-memory event stream
    lines = [json.loads(line) for line in
             (tmp_path / "ckpt" / "telemetry.jsonl").open()]
    assert [e["event"] for e in lines] == [e["event"] for e in res.events]
    assert sum(e["event"] == "stage_done" for e in lines) == len(STAGES)


@pytest.mark.parametrize("stage", STAGES)
def test_resume_bit_identity_after_crash(scenario, reference, tmp_path,
                                         stage):
    """Kill at every stage boundary; the resumed run must reproduce the
    uninterrupted run bit for bit (the tentpole acceptance gate)."""
    batch, params = scenario
    root = tmp_path / "ckpt"
    with pytest.raises(InjectedCrash):
        run_resilient(batch, params, checkpoint_dir=root,
                      fault_plan=FaultPlan(crash_at=stage))
    res = run_resilient(batch, params, checkpoint_dir=root)
    assert res.resumed_from == STAGES.index(stage)
    assert_bit_identical(res.output, reference)


# -------------------------------------------------------- overflow policy


def test_overflow_widen_recovers_dense_labels(scenario, reference):
    batch, params = scenario
    res = run_resilient(batch, params, plan=TOPK2, on_overflow="widen")
    assert res.widen_count >= 1
    r, q = res.output.result, reference.result
    assert (np.asarray(r.member_of) == np.asarray(q.member_of)).all()
    assert (np.asarray(r.is_rep) == np.asarray(q.is_rep)).all()
    assert int(res.output.sim_overflow) == 0


def test_overflow_degrade_records_certificate(scenario):
    batch, params = scenario
    res = run_resilient(batch, params, plan=TOPK2, on_overflow="degrade")
    assert res.widen_count == 0
    assert int(res.output.sim_overflow) > 0
    assert any(e["event"] == "overflow_degraded" for e in res.events)


def test_overflow_raise(scenario):
    batch, params = scenario
    with pytest.raises(OverflowViolation, match="sim_topk"):
        run_resilient(batch, params, plan=TOPK2, on_overflow="raise")


def test_overflow_widen_applies_to_restored_state(scenario, reference,
                                                  tmp_path):
    """A run directory whose newest checkpoint holds an overflowed
    cluster state (here: a completed degrade run) must widen on resume
    under on_overflow='widen' — the policy applies to restored state,
    not only to freshly-computed cluster output."""
    batch, params = scenario
    root = tmp_path / "ckpt"
    res0 = run_resilient(batch, params, checkpoint_dir=root, plan=TOPK2,
                         on_overflow="degrade")
    assert int(res0.output.sim_overflow) > 0
    res = run_resilient(batch, params, checkpoint_dir=root, plan=TOPK2,
                        on_overflow="widen")
    assert res.widen_count >= 1
    r, q = res.output.result, reference.result
    assert (np.asarray(r.member_of) == np.asarray(q.member_of)).all()
    assert int(res.output.sim_overflow) == 0


def test_bad_policy_values(scenario):
    batch, params = scenario
    with pytest.raises(ValueError, match="on_overflow"):
        run_resilient(batch, params, on_overflow="explode")
    with pytest.raises(ValueError, match="on_corruption"):
        run_resilient(batch, params, on_corruption="shrug")


# ------------------------------------------------------- transient faults


def test_transient_retry_schedule(scenario, reference):
    batch, params = scenario
    delays = []
    res = run_resilient(batch, params,
                        fault_plan=FaultPlan(transient_at="segment",
                                             transient_count=2),
                        max_retries=3, sleep=delays.append)
    assert delays == [0.5, 1.0]      # bounded exponential backoff
    retries = [e for e in res.events if e["event"] == "retry"]
    assert [e["attempt"] for e in retries] == [1, 2]
    assert all(e["stage"] == "segment" for e in retries)
    assert_bit_identical(res.output, reference)


def test_transient_retries_exhausted(scenario):
    batch, params = scenario
    with pytest.raises(RetriesExhausted):
        run_resilient(batch, params,
                      fault_plan=FaultPlan(transient_at="similarity",
                                           transient_count=9),
                      max_retries=2, sleep=lambda s: None)


# -------------------------------------------------- checkpoint corruption


def test_corrupted_checkpoint_falls_back_a_step(scenario, reference,
                                                tmp_path):
    batch, params = scenario
    root = tmp_path / "ckpt"
    with pytest.raises(InjectedCrash):
        run_resilient(batch, params, checkpoint_dir=root,
                      fault_plan=FaultPlan(corrupt_stage="similarity",
                                           crash_at="cluster"))
    res = run_resilient(batch, params, checkpoint_dir=root)
    sim_step = STAGES.index("similarity") + 1
    assert res.fallback_steps == [sim_step]
    assert res.resumed_from == sim_step - 1
    assert any(e["event"] == "checkpoint_fallback" for e in res.events)
    assert_bit_identical(res.output, reference)


def test_corruption_fail_policy(scenario, tmp_path):
    batch, params = scenario
    root = tmp_path / "ckpt"
    with pytest.raises(InjectedCrash):
        run_resilient(batch, params, checkpoint_dir=root,
                      fault_plan=FaultPlan(corrupt_stage="segment",
                                           crash_at="similarity"))
    with pytest.raises(CheckpointCorruption):
        run_resilient(batch, params, checkpoint_dir=root,
                      on_corruption="fail")


# ---------------------------------------------------------- FaultPlan api


def test_fault_plan_roundtrip(tmp_path):
    fp = FaultPlan(crash_at="cluster", transient_at="join",
                   transient_count=2, corrupt_stage="segment",
                   corrupt_leaf=3, slow=(("join", 1, 2.5),))
    assert FaultPlan.from_json(fp.to_json()) == fp
    p = tmp_path / "faults.json"
    fp.save(p)
    assert FaultPlan.load(p) == fp


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="crash_at"):
        FaultPlan(crash_at="warmup").validate()
    with pytest.raises(ValueError, match="transient_count"):
        FaultPlan(transient_count=-1).validate()
    with pytest.raises(ValueError, match="without transient_at"):
        FaultPlan(transient_count=2).validate()
    with pytest.raises(ValueError, match="slow entry"):
        FaultPlan(slow=(("join", 0),)).validate()
    with pytest.raises(ValueError, match="unknown FaultPlan fields"):
        FaultPlan.from_dict({"crash_on": "join"})
    # replace() re-validates
    with pytest.raises(ValueError, match="corrupt_leaf"):
        FaultPlan().replace(corrupt_leaf=-1)


def test_fault_plan_slowdown_accumulates():
    fp = FaultPlan(slow=(("join", 1, 2.0), ("join", 1, 0.5),
                         ("cluster", 0, 9.0)))
    assert fp.slowdown("join", 1) == 2.5
    assert fp.slowdown("join", 0) == 0.0
    assert fp.slowdown("cluster", 0) == 9.0


def test_injector_transient_counts_are_per_process():
    inj = FaultInjector(FaultPlan(transient_at="join", transient_count=2))
    for _ in range(2):
        with pytest.raises(TransientFault):
            inj.on_stage_enter("join")
    inj.on_stage_enter("join")       # third attempt succeeds
    inj.on_stage_enter("segment")    # other stages never fault


# ---------------------------------------------------- retry_with_backoff


def test_retry_backoff_schedule_caps_at_max_delay():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 5:
            raise TransientFault("boom")
        return "ok"

    delays = []
    out = retry_with_backoff(flaky, max_retries=8, base_delay=1.0,
                             max_delay=4.0, sleep=delays.append)
    assert out == "ok"
    assert delays == [1.0, 2.0, 4.0, 4.0, 4.0]


def test_retry_backoff_exhaustion_chains_cause():
    def always():
        raise TransientFault("persistent")

    with pytest.raises(RetriesExhausted) as ei:
        retry_with_backoff(always, max_retries=2, sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, TransientFault)


def test_retry_backoff_ignores_nonretryable():
    def bad():
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry_with_backoff(bad, sleep=lambda s: None)


# ------------------------------------------------------------ checkpointer


def test_checkpoint_flat_roundtrip(tmp_path):
    tree = {"a": np.arange(6, dtype=np.int32).reshape(2, 3),
            "b/c": np.linspace(0.0, 1.0, 4, dtype=np.float32)}
    save_checkpoint(tmp_path, 3, tree)
    got, step = load_checkpoint_flat(tmp_path)
    assert step == 3
    assert set(got) == set(tree)
    for k in tree:
        assert got[k].dtype == tree[k].dtype
        assert (got[k] == tree[k]).all()


def test_checkpoint_dtype_mismatch_raises(tmp_path):
    save_checkpoint(tmp_path, 1, {"x": np.zeros(4, np.float32)})
    with pytest.raises(ValueError, match="dtype"):
        load_checkpoint(tmp_path, {"x": np.zeros(4, np.int32)}, step=1)


def test_checkpoint_crc_detects_bitrot(tmp_path):
    save_checkpoint(tmp_path, 1, {"x": np.arange(64, dtype=np.float64)})
    inj = FaultInjector(FaultPlan(corrupt_stage="join", corrupt_leaf=0))
    assert inj.on_checkpoint_written("join", tmp_path / "step_000000001")
    with pytest.raises(IOError, match="checksum mismatch"):
        load_checkpoint_flat(tmp_path, step=1)
    # verify=False reads the damaged bytes without the integrity gate
    got, _ = load_checkpoint_flat(tmp_path, step=1, verify=False)
    assert got["x"].shape == (64,)


# --------------------------------------------------------------- straggler


def test_straggler_monitor_empty_is_silent():
    mon = StragglerMonitor(n_hosts=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert mon.check() == {}


def test_straggler_flag_and_reset():
    mon = StragglerMonitor(n_hosts=4, window=4)
    for _ in range(4):
        mon.record_all([1.0, 1.0, 1.0, 5.0])
    flagged = mon.check()
    assert list(flagged) == [3] and flagged[3] >= 1.5
    mon.reset(3)
    assert mon.flagged == {} and len(mon.history[3]) == 0
    # a clean restart of the rank must not re-flag from stale history
    mon.record_all([1.0, 1.0, 1.0, 1.0])
    assert mon.check() == {}


def test_suggest_rebalance_edges_narrows_slow_partition():
    rng = np.random.default_rng(0)
    times = np.sort(rng.uniform(0.0, 100.0, size=400))
    part_of = np.minimum(np.arange(400) // 100, 3)
    edges = suggest_rebalance_edges(times, part_of, {1: 3.0}, P=4)
    assert edges.shape == (5,)
    assert edges[0] == -np.inf and edges[-1] == np.inf
    assert (np.diff(edges[1:-1]) >= 0).all()
    # partition 1's time span shrinks: its points weigh 3x, so the
    # weighted equi-depth quantiles pull both its edges inward
    old_span = times[199] - times[100]
    new_span = edges[2] - edges[1]
    assert new_span < old_span


def test_slowdown_feeds_straggler_telemetry(scenario):
    """Scripted slowdowns on one partition must surface as per-partition
    timings in the stage_done telemetry (the wiring the distributed
    driver asserts end to end with flags + rebalance edges)."""
    batch, params = scenario
    slow = tuple((s, 0, 30.0) for s in STAGES)
    res = run_resilient(batch, params, fault_plan=FaultPlan(slow=slow))
    done = [e for e in res.events if e["event"] == "stage_done"]
    assert len(done) == len(STAGES)
    assert all(e["per_partition_s"][0] >= 30.0 for e in done)


# ------------------------------------------------- launcher exit codes


@pytest.fixture(scope="module")
def launcher_codes(tmp_path_factory):
    """One subprocess per failure class through the real CLI; returns
    {name: returncode}."""
    tmp = tmp_path_factory.mktemp("launcher")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)

    def run(extra):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.run_dsc",
             "--n-trajs", "12"] + extra,
            env=env, capture_output=True, text=True, timeout=600)
        return proc.returncode

    crash = tmp / "crash.json"
    FaultPlan(crash_at="cluster").save(crash)
    transient = tmp / "transient.json"
    FaultPlan(transient_at="segment", transient_count=9).save(transient)
    corrupt = tmp / "corrupt.json"
    FaultPlan(corrupt_stage="segment", crash_at="similarity").save(corrupt)
    topk2 = tmp / "topk2.json"
    TOPK2.save(topk2)

    codes = {}
    codes["crash"] = run(["--resume-dir", str(tmp / "c1"),
                          "--fault-plan", str(crash)])
    codes["resume"] = run(["--resume-dir", str(tmp / "c1")])
    codes["retries"] = run(["--fault-plan", str(transient),
                            "--max-retries", "1"])
    codes["corrupt_crash"] = run(["--resume-dir", str(tmp / "c2"),
                                  "--fault-plan", str(corrupt)])
    codes["resume_fail"] = run(["--resume-dir", str(tmp / "c2"),
                                "--on-corruption", "fail"])
    codes["resume_fallback"] = run(["--resume-dir", str(tmp / "c2")])
    codes["overflow_raise"] = run(["--plan", str(topk2),
                                   "--on-overflow", "raise"])
    codes["overflow_widen"] = run(["--plan", str(topk2),
                                   "--on-overflow", "widen"])
    return codes


@pytest.mark.slow
def test_launcher_exit_code_matrix(launcher_codes):
    c = launcher_codes
    assert c["crash"] == EXIT_CODES["injected_crash"]
    assert c["corrupt_crash"] == EXIT_CODES["injected_crash"]
    assert c["retries"] == EXIT_CODES["retries_exhausted"]
    assert c["resume_fail"] == EXIT_CODES["corruption"]
    assert c["overflow_raise"] == EXIT_CODES["overflow"]
    # every failure class maps to a distinct nonzero code
    fails = [c["crash"], c["retries"], c["resume_fail"],
             c["overflow_raise"]]
    assert 0 not in fails and len(set(fails)) == len(fails)


@pytest.mark.slow
def test_launcher_recovers_after_faults(launcher_codes):
    assert launcher_codes["resume"] == EXIT_CODES["ok"]
    assert launcher_codes["resume_fallback"] == EXIT_CODES["ok"]
    assert launcher_codes["overflow_widen"] == EXIT_CODES["ok"]


# -------------------------------------------- telemetry hardening (S2)


def test_telemetry_schema_and_crash_parse(scenario, tmp_path):
    """Kill mid-run; the fsynced JSONL must parse completely, and every
    event carries the schema version."""
    from repro.run import TELEMETRY_SCHEMA, read_telemetry
    batch, params = scenario
    root = tmp_path / "ckpt"
    with pytest.raises(InjectedCrash):
        run_resilient(batch, params, checkpoint_dir=root,
                      fault_plan=FaultPlan(crash_at="cluster"))
    events = read_telemetry(root / "telemetry.jsonl")
    assert events and all(e["schema"] == TELEMETRY_SCHEMA for e in events)
    assert [e for e in events if e["event"] == "stage_done"]


def test_read_telemetry_tolerates_torn_tail(tmp_path):
    from repro.run import read_telemetry
    p = tmp_path / "telemetry.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"schema": 1, "event": "a"}) + "\n")
        f.write(json.dumps({"schema": 1, "event": "b"}) + "\n")
        f.write('{"schema": 1, "event": "c", "tru')      # crash mid-write
    events = read_telemetry(p)
    assert [e["event"] for e in events] == ["a", "b"]


def test_read_telemetry_rejects_mid_file_damage(tmp_path):
    from repro.run import read_telemetry
    p = tmp_path / "telemetry.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"event": "a"}) + "\n")
        f.write("garbage not json\n")
        f.write(json.dumps({"event": "b"}) + "\n")
    with pytest.raises(ValueError, match="line 2"):
        read_telemetry(p)


# ------------------------------------------------- async saves (S3)


def test_sync_saves_escape_hatch_same_resume(scenario, reference,
                                             tmp_path):
    """Async (default) and synchronous checkpointing must leave
    identical resume points and bit-identical outputs."""
    batch, params = scenario
    results = {}
    for name, sync in (("async", False), ("sync", True)):
        root = tmp_path / name
        with pytest.raises(InjectedCrash):
            run_resilient(batch, params, checkpoint_dir=root,
                          fault_plan=FaultPlan(crash_at="cluster"),
                          sync_saves=sync)
        mgr = CheckpointManager(root)
        assert mgr.available_steps() == [1, 2, 3], name
        res = run_resilient(batch, params, checkpoint_dir=root,
                            sync_saves=sync)
        assert res.resumed_from == STAGES.index("cluster")
        assert_bit_identical(res.output, reference)
        results[name] = res
    assert results["async"].sscr == results["sync"].sscr


# ------------------------------- retry bounds + truncated leaves (S4)


def test_retry_exact_attempt_count_on_exhaustion():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise TransientFault("persistent")

    with pytest.raises(RetriesExhausted):
        retry_with_backoff(always, max_retries=3, sleep=lambda s: None)
    assert calls["n"] == 4              # 1 initial + max_retries retries


def test_retry_zero_retries_fails_after_first_attempt():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise TransientFault("persistent")

    with pytest.raises(RetriesExhausted):
        retry_with_backoff(always, max_retries=0, sleep=lambda s: None)
    assert calls["n"] == 1


def test_injected_clock_drives_telemetry_timestamps(scenario):
    batch, params = scenario
    tick = {"n": 0}

    def clock():
        tick["n"] += 1
        return float(tick["n"])

    res = run_resilient(batch, params, clock=clock)
    ts = [e["ts"] for e in res.events]
    assert ts == sorted(ts) and all(float(t).is_integer() for t in ts)


def test_truncated_checkpoint_leaf_detected_and_skipped(scenario,
                                                        reference,
                                                        tmp_path):
    """A leaf file cut short (disk-full / partial write) must fail the
    load — np.load or the CRC gate — and fallback must recover from the
    previous step."""
    batch, params = scenario
    root = tmp_path / "ckpt"
    run_resilient(batch, params, checkpoint_dir=root)
    mgr = CheckpointManager(root)
    last = mgr.available_steps()[-1]
    leaves = sorted(mgr.step_dir(last).glob("leaf_*.npy"))
    os.truncate(leaves[0], max(1, leaves[0].stat().st_size // 2))
    with pytest.raises((IOError, EOFError, ValueError)):
        load_checkpoint_flat(root, step=last)
    res = run_resilient(batch, params, checkpoint_dir=root)
    assert res.fallback_steps == [last]
    assert res.resumed_from == last - 1
    assert_bit_identical(res.output, reference)


# ----------------------------------- FaultPlan/P validation (S1)


def test_slow_partition_out_of_range_raises(scenario):
    batch, params = scenario
    with pytest.raises(ValueError, match="partition"):
        run_resilient(batch, params,
                      fault_plan=FaultPlan(slow=(("join", 3, 1.0),)))


# ------------------------------------------------- RebalancePolicy api


def test_rebalance_policy_roundtrip(tmp_path):
    from repro.run import RebalancePolicy
    pol = RebalancePolicy(mode="apply", consecutive=2, max_applies=3)
    assert RebalancePolicy.from_json(pol.to_json()) == pol
    p = tmp_path / "rebalance.json"
    pol.save(p)
    assert RebalancePolicy.load(p) == pol


def test_rebalance_policy_validation():
    from repro.run import RebalancePolicy
    with pytest.raises(ValueError, match="mode"):
        RebalancePolicy(mode="sometimes").validate()
    with pytest.raises(ValueError, match="consecutive"):
        RebalancePolicy(consecutive=0).validate()
    with pytest.raises(ValueError, match="max_applies"):
        RebalancePolicy(max_applies=-1).validate()
    with pytest.raises(ValueError, match="unknown RebalancePolicy"):
        RebalancePolicy.from_dict({"mode": "apply", "threshold": 2})


def test_rebalance_policy_rejected_at_run_start(scenario):
    from repro.run import RebalancePolicy
    batch, params = scenario
    with pytest.raises(ValueError, match="mode"):
        run_resilient(batch, params,
                      rebalance=RebalancePolicy(mode="bogus"))
