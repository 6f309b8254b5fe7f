"""Streaming ingestion + windowed incremental DSC (DESIGN.md §13).

Covers the full robustness surface: the quarantine matrix per reason and
policy, watermark/lateness semantics, backpressure under both policies,
the streaming-vs-batch bit-parity anchor (standing lists, spill, labels
vs ``run_dsc`` over the same window), warm-vs-cold clustering identity,
kill-and-resume bit-identity after every Nth advance, dirty/late chaos
with the exact accounting invariant, the telemetry event stream, and the
launcher's stream exit codes (7 poison, 8 backpressure) as real
subprocesses.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.synthetic import dirtify, figure1_scenario, stream_records
from repro.run.faults import FaultInjector, FaultPlan, InjectedCrash
from repro.run.resilient import EXIT_CODES, Telemetry, read_telemetry
from repro.serve.stream import StreamService
from repro.stream import (QUARANTINE_REASONS, BackpressureOverflow,
                          Ingestor, PoisonRecord, Records, StreamConfig,
                          StreamDriver, WatermarkStall, WindowManager)

pytestmark = pytest.mark.stream


def small_config(**kw):
    base = dict(t_cap=16, m_cap=16, eps_sp=0.3, eps_t=2.0, alpha_abs=0.1,
                k_abs=0.0, allowed_lateness=4.0, horizon=1000.0,
                max_subs=4, k=8, w=2)
    base.update(kw)
    return StreamConfig(**base)


def small_stream(batch_size=24, **kw):
    batch, _ = figure1_scenario(n_per_route=2, points_per_leg=8, **kw)
    return batch, stream_records(batch, batch_size=batch_size)


# ---------------------------------------------------------------- ingest

def test_ingest_quarantine_reasons():
    ing = Ingestor(on_dirty="drop", max_speed=1.0)
    # clean baseline fix for obj 1 (teleport anchor)
    ing.process(Records.build([1], [0.0], [0.0], [0.0]))
    # nonfinite / duplicate / non-monotone / teleport in one submission
    recs = Records.build([1, 1, 1, 1, 1],
                         [np.nan, 1.0, 1.1, 99.0, 1.2],
                         [0.0, 0.0, 0.0, 0.0, 0.0],
                         [1.0, 2.0, 1.5, 3.0, 4.0])
    out = ing.process(recs)
    assert ing.counters["nonfinite"] == 1
    assert ing.counters["non_monotone"] == 1   # t=1.5 after t=2.0 admitted
    assert ing.counters["teleport"] == 1       # 98 units in 1s vs max 1/s
    assert out.n == 2                          # t=2.0 and t=4.0 survive
    dup = ing.process(Records.build([1], [1.2], [0.0], [4.0]))
    assert dup.n == 0 and ing.counters["duplicate"] == 1
    # every rejection is logged with its reason
    reasons = sorted(e["reason"] for e in ing.quarantine_log())
    assert reasons == sorted(
        ["nonfinite", "non_monotone", "teleport", "duplicate"])
    assert ing.submitted == 7
    assert ing.admitted + ing.quarantined_total() == 7


def test_ingest_repair_sorts_in_batch_swaps():
    rep = Ingestor(on_dirty="repair")
    out = rep.process(Records.build([5, 5, 5], [0.0, 1.0, 2.0],
                                    [0.0, 0.0, 0.0], [2.0, 1.0, 3.0]))
    assert out.n == 3 and list(out.t) == [1.0, 2.0, 3.0]
    assert rep.repaired_order > 0
    assert rep.counters["non_monotone"] == 0
    # drop mode quarantines the same swap instead of fixing it
    drp = Ingestor(on_dirty="drop")
    out = drp.process(Records.build([5, 5, 5], [0.0, 1.0, 2.0],
                                    [0.0, 0.0, 0.0], [2.0, 1.0, 3.0]))
    assert out.n == 2 and drp.counters["non_monotone"] == 1


def test_ingest_fail_mode_raises_poison():
    ing = Ingestor(on_dirty="fail")
    with pytest.raises(PoisonRecord):
        ing.process(Records.build([1], [np.nan], [0.0], [0.0]))


def test_ingest_state_roundtrip():
    ing = Ingestor(on_dirty="drop", max_speed=1.0)
    ing.process(Records.build([1, 2, 1], [0.0, 1.0, np.nan],
                              [0.0, 0.0, 0.0], [0.0, 0.0, 1.0]))
    st = ing.state_arrays()
    ing2 = Ingestor(on_dirty="drop", max_speed=1.0)
    ing2.load_state_arrays(st)
    assert ing2.counters == ing.counters
    assert ing2.submitted == ing.submitted
    assert ing2.admitted == ing.admitted
    assert ing2.quarantine_log() == ing.quarantine_log()
    assert ing2._last == ing._last


# --------------------------------------------------------------- dirtify

def test_dirtify_deterministic_with_ground_truth_counts():
    batch, batches = small_stream()
    d1, t1 = dirtify(batches, dup_frac=0.1, nan_frac=0.05,
                     teleport_frac=0.05, seed=11)
    d2, t2 = dirtify(batches, dup_frac=0.1, nan_frac=0.05,
                     teleport_frac=0.05, seed=11)
    assert t1 == t2
    for a, b in zip(d1, d2):
        for f in Records._fields:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert t1["dup"] > 0 and t1["nan"] > 0 and t1["teleport"] > 0
    # ingest counters match the injected ground truth exactly — one
    # corruption at a time (a dup of a teleported record quarantines as
    # teleport, so combined runs only bound the per-reason totals)
    for kw, reason, key in ((dict(nan_frac=0.1), "nonfinite", "nan"),
                            (dict(dup_frac=0.1), "duplicate", "dup"),
                            (dict(teleport_frac=0.1), "teleport",
                             "teleport")):
        dirty, truth = dirtify(batches, seed=7, **kw)
        ing = Ingestor(on_dirty="drop", max_speed=5.0)
        for recs in dirty:
            ing.process(recs)
        assert truth[key] > 0
        assert ing.counters[reason] == truth[key], (reason, ing.counters)


def test_dirtify_swaps_are_repairable():
    batch, batches = small_stream()
    # traj-order stream has adjacent same-object records to swap
    batches = stream_records(batch, batch_size=24, order="traj")
    dirty, truth = dirtify(batches, swap_frac=0.5, seed=3)
    assert truth["swap_pairs"] > 0
    ing = Ingestor(on_dirty="repair")
    n_admitted = sum(ing.process(r).n for r in dirty)
    assert ing.counters["non_monotone"] == 0      # repair fixed every swap
    assert n_admitted == sum(r.n for r in dirty)
    assert ing.repaired_order > 0


# ---------------------------------------------------------------- window

def test_watermark_monotone_and_late_dropped():
    wm = WindowManager(allowed_lateness=2.0, horizon=10.0)
    wm.stage(Records.build([1, 1], [0, 0], [0, 0], [10.0, 5.0]))
    admitted, late = wm.drain()
    assert wm.watermark == 8.0            # max(10) - 2
    assert late == 1 and admitted.n == 1  # t=5 < 8 dropped, counted
    # watermark never regresses
    wm.stage(Records.build([1], [0], [0], [3.0]))
    admitted, late = wm.drain()
    assert wm.watermark == 8.0 and late == 1 and admitted.n == 0
    assert wm.late_dropped == 2
    assert wm.evict_before() == pytest.approx(-2.0)


def test_watermark_stall_raises():
    wm = WindowManager(allowed_lateness=5.0, horizon=10.0,
                       stall_advances=2)
    wm.stage(Records.build([1], [0], [0], [100.0]))
    wm.drain()                                     # W = 95
    wm.stage(Records.build([1], [0], [0], [10.0]))
    wm.drain()                                     # stalled once
    wm.stage(Records.build([1], [0], [0], [11.0]))
    with pytest.raises(WatermarkStall):
        wm.drain()                                 # stalled twice


def test_backpressure_shed_oldest_counts_everything():
    wm = WindowManager(allowed_lateness=1.0, horizon=10.0, queue_cap=5,
                       policy="shed_oldest")
    wm.stage(Records.build(np.arange(4), np.zeros(4), np.zeros(4),
                           np.arange(4, dtype=float)))
    shed = wm.stage(Records.build(np.arange(4), np.zeros(4), np.zeros(4),
                                  4.0 + np.arange(4, dtype=float)))
    assert shed == 3 and wm.shed == 3 and wm.queued() == 5
    assert wm.staged_total == 8            # nothing vanished unaccounted


def test_backpressure_block_raises_and_undoes():
    wm = WindowManager(allowed_lateness=5.0, horizon=10.0, queue_cap=5,
                       policy="block")
    wm.stage(Records.build(np.arange(4), np.zeros(4), np.zeros(4),
                           np.arange(4, dtype=float)))
    with pytest.raises(BackpressureOverflow):
        wm.stage(Records.build(np.arange(4), np.zeros(4), np.zeros(4),
                               4.0 + np.arange(4, dtype=float)))
    assert wm.queued() == 4                # the enqueue was rolled back
    admitted, _ = wm.drain()
    assert admitted.n == 4                 # earlier records intact


# ------------------------------------------------- streaming == batch DSC

def drive(cfg, batches, **svc_kw):
    svc = StreamService(cfg, **svc_kw)
    svc.run(batches)
    return svc


def assert_matches_batch_oracle(drv):
    """Standing lists, spill and labels == run_dsc over the same window."""
    from repro.core.dsc import run_dsc
    from repro.core.plan import EnginePlan
    out = run_dsc(drv.window_batch(), drv.config.params,
                  plan=EnginePlan(sim_mode="topk", sim_topk=drv.config.k),
                  on_overflow="degrade")
    K = drv.config.k
    np.testing.assert_array_equal(np.asarray(out.sim_topk.ids),
                                  drv.standing_ids[:, :K])
    np.testing.assert_array_equal(np.asarray(out.sim_topk.sims),
                                  drv.standing_sims[:, :K])
    np.testing.assert_array_equal(np.asarray(out.sim_topk.spill),
                                  drv.standing_sims[:, K])
    r = out.result
    np.testing.assert_array_equal(np.asarray(r.member_of), drv.member_of)
    np.testing.assert_array_equal(np.asarray(r.member_sim), drv.member_sim)
    np.testing.assert_array_equal(np.asarray(r.is_rep), drv.is_rep)
    np.testing.assert_array_equal(np.asarray(r.is_outlier), drv.is_outlier)


def test_streaming_matches_batch_at_every_advance():
    batch, batches = small_stream()
    cfg = small_config()
    svc = StreamService(cfg)
    for i, recs in enumerate(batches):
        svc.driver.submit(recs)
        svc.driver.advance()
        assert_matches_batch_oracle(svc.driver)
    assert svc.accounting()["balanced"]
    assert svc.stats()["reps"] > 0


def test_streaming_matches_batch_with_eviction():
    batch, batches = small_stream()
    cfg = small_config(horizon=8.0, allowed_lateness=2.0)
    svc = StreamService(cfg)
    evicted = 0
    for recs in batches:
        svc.driver.submit(recs)
        s = svc.driver.advance()
        evicted += s.get("evicted", 0) if isinstance(s, dict) else 0
        assert_matches_batch_oracle(svc.driver)
    assert evicted > 0                    # the horizon actually evicted
    assert svc.accounting()["balanced"]


def test_warm_start_labels_equal_cold_start():
    batch, batches = small_stream()
    warm = drive(small_config(warm_start=True), batches)
    cold = drive(small_config(warm_start=False), batches)
    for attr in ("standing_ids", "standing_sims", "member_of",
                 "member_sim", "is_rep", "is_outlier"):
        np.testing.assert_array_equal(getattr(warm.driver, attr),
                                      getattr(cold.driver, attr))


def test_row_capacity_overflow_drops_oldest_and_counts():
    cfg = small_config(t_cap=4, m_cap=4, allowed_lateness=100.0)
    drv = StreamDriver(cfg)
    recs = Records.build([7] * 6, np.arange(6, dtype=float),
                         np.zeros(6), np.arange(6, dtype=float))
    drv.submit(recs)
    drv.advance()
    assert drv.row_overflow == 2          # 6 points into a 4-slot row
    r = drv._row_of[7]
    np.testing.assert_array_equal(drv.ts[r][drv.valid[r]],
                                  [2.0, 3.0, 4.0, 5.0])
    assert drv.accounting()["balanced"]


# ---------------------------------------------------------- kill + resume

def reference_run(cfg, batches, tmp_path, tag):
    svc = StreamService(cfg, checkpoint_dir=str(tmp_path / tag))
    svc.run(batches)
    return svc


def state_fingerprint(svc):
    d = svc.driver
    return {
        "ids": d.standing_ids.copy(), "sims": d.standing_sims.copy(),
        "member_of": d.member_of.copy(), "is_rep": d.is_rep.copy(),
        "is_outlier": d.is_outlier.copy(), "valid": d.valid.copy(),
        "ts": d.ts.copy(), "quarantine": dict(d.ingest.counters),
        "stats": svc.stats(), "accounting": svc.accounting(),
        "qlog": d.ingest.quarantine_log(),
    }


def assert_same_state(a, b):
    for k in a:
        if isinstance(a[k], np.ndarray):
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        else:
            assert a[k] == b[k], k


@pytest.mark.slow
def test_kill_and_resume_at_every_advance(tmp_path):
    """Kill at every Nth window advance; the resumed service must land
    bit-identically on the uninterrupted run — lists, labels, window
    contents, quarantine books, the lot."""
    batch, batches = small_stream()
    cfg = small_config(snapshot_every=1)
    ref = state_fingerprint(reference_run(cfg, batches, tmp_path, "ref"))
    n_adv = len(batches)
    for kill_at in range(1, n_adv):
        ck = str(tmp_path / f"kill{kill_at}")
        inj = FaultInjector(FaultPlan(crash_at_advance=kill_at))
        svc = StreamService(cfg, checkpoint_dir=ck, injector=inj)
        with pytest.raises(InjectedCrash):
            svc.run(batches)
        # resumed run: NO fault plan (the crash already happened)
        svc2 = StreamService(cfg, checkpoint_dir=ck)
        assert svc2.resumed and svc2.driver.advance_count == kill_at
        svc2.run(batches)
        assert_same_state(ref, state_fingerprint(svc2))


def test_kill_and_resume_once(tmp_path):
    """Tier-1-speed single-kill variant of the full matrix above."""
    batch, batches = small_stream()
    cfg = small_config(snapshot_every=1)
    ref = state_fingerprint(reference_run(cfg, batches, tmp_path, "ref"))
    ck = str(tmp_path / "kill")
    inj = FaultInjector(FaultPlan(crash_at_advance=3))
    svc = StreamService(cfg, checkpoint_dir=ck, injector=inj)
    with pytest.raises(InjectedCrash):
        svc.run(batches)
    svc2 = StreamService(cfg, checkpoint_dir=ck)
    assert svc2.resumed
    svc2.run(batches)
    assert_same_state(ref, state_fingerprint(svc2))


def test_resume_refuses_other_config(tmp_path):
    batch, batches = small_stream()
    cfg = small_config(snapshot_every=1)
    svc = StreamService(cfg, checkpoint_dir=str(tmp_path / "ck"))
    svc.run(batches[:2])
    other = small_config(snapshot_every=1, eps_sp=0.31)
    with pytest.raises(ValueError, match="different schema/config"):
        StreamService(other, checkpoint_dir=str(tmp_path / "ck"))


def test_snapshot_refuses_nonempty_queue(tmp_path):
    batch, batches = small_stream()
    cfg = small_config()
    drv = StreamDriver(cfg, checkpoint_dir=str(tmp_path / "ck"))
    drv.submit(batches[0])
    with pytest.raises(RuntimeError, match="staging queue"):
        drv.snapshot()


# ------------------------------------------------------------ chaos suite

def test_chaos_never_crashes_and_accounts_for_everything():
    """Under scripted dirty/late/dup chaos the service must keep
    serving — no exception, and the accounting invariant holds exactly:
    every submitted record is inserted, quarantined, late-dropped, shed,
    or still queued."""
    batch, batches = small_stream()
    plan = FaultPlan(stream_late_burst=((2, 50.0), (5, 120.0)),
                     stream_dup_storm=(3, 6), stream_poison=((1, 4),),
                     stream_stall=(4,))
    svc = StreamService(small_config(max_speed=100.0),
                        injector=FaultInjector(plan))
    svc.run(batches)
    acc = svc.accounting()
    assert acc["balanced"], acc
    assert acc["quarantined"] > 0          # poison + dup storms were booked
    assert acc["late_dropped"] > 0         # the late bursts were counted
    assert svc.driver.ingest.counters["nonfinite"] >= 1
    assert svc.driver.ingest.counters["duplicate"] > 0


def test_chaos_dirty_stream_still_matches_batch_of_admitted():
    """Even under chaos the standing state equals the batch pipeline run
    over exactly the records that were admitted."""
    batch, batches = small_stream()
    plan = FaultPlan(stream_poison=((0, 2), (3, 7)))
    svc = StreamService(small_config(), injector=FaultInjector(plan))
    svc.run(batches)
    assert_matches_batch_oracle(svc.driver)


def test_stall_batches_defer_advance():
    batch, batches = small_stream()
    plan = FaultPlan(stream_stall=tuple(range(len(batches) - 1)))
    svc = StreamService(small_config(), injector=FaultInjector(plan))
    svc.run(batches)
    # all advances deferred to the final drain => exactly one advance
    assert svc.driver.advance_count == 1
    assert svc.accounting()["balanced"]


# ------------------------------------------------------------- telemetry

def test_stream_telemetry_events(tmp_path):
    path = tmp_path / "telemetry.jsonl"
    clock_t = [0.0]

    def clock():
        clock_t[0] += 1.0
        return clock_t[0]

    batch, batches = small_stream()
    plan = FaultPlan(stream_late_burst=((2, 80.0),), stream_poison=((1, 0),))
    svc = StreamService(small_config(queue_cap=16),
                        telemetry=Telemetry(path, clock),
                        injector=FaultInjector(plan))
    svc.run(batches)
    events = read_telemetry(path)
    by = {}
    for e in events:
        by.setdefault(e["event"], []).append(e)
    assert "window_advanced" in by
    assert "record_quarantined" in by       # the poison record
    assert "late_dropped" in by             # the late burst
    assert "backpressure" in by             # queue_cap=30 < batch size
    adv = by["window_advanced"][-1]
    for key in ("advance", "watermark", "dirty_rows", "rounds",
                "warm_prefix", "reps", "outliers"):
        assert key in adv, key
    q = by["record_quarantined"][0]
    assert q["total"] >= 1 and q.get("nonfinite", 0) >= 1
    # events survive a reader round-trip with the schema tag intact
    assert all(e["schema"] == 1 for e in events)


# ------------------------------------------------------- config validation

def test_stream_config_validation():
    with pytest.raises(ValueError, match="absolute thresholds"):
        small_config(alpha_abs=-1.0).validate()
    with pytest.raises(ValueError, match="horizon"):
        small_config(horizon=1.0, allowed_lateness=5.0).validate()
    with pytest.raises(ValueError, match="segmentation"):
        small_config(segmentation="nope").validate()
    with pytest.raises(ValueError, match="backpressure"):
        small_config(backpressure="nope").validate()
    cfg = small_config()
    assert StreamConfig.from_dict(cfg.to_dict()) == cfg
    assert cfg.fingerprint() == StreamConfig.from_dict(
        cfg.to_dict()).fingerprint()
    assert cfg.fingerprint() != small_config(eps_sp=0.31).fingerprint()


def test_fault_plan_stream_fields_roundtrip():
    plan = FaultPlan(stream_late_burst=((2, 50.0),), stream_dup_storm=(3,),
                     stream_poison=((1, 4),), stream_stall=(4,),
                     crash_at_advance=7)
    back = FaultPlan.from_json(plan.to_json())
    assert back == plan
    with pytest.raises(ValueError, match="crash_at_advance"):
        FaultPlan(crash_at_advance=-2).validate()
    with pytest.raises(ValueError, match="stream_poison"):
        FaultPlan(stream_poison=((-1, 0),)).validate()


# ---------------------------------------------------------------- queries

def test_query_api_reports_cluster_membership():
    batch, batches = small_stream()
    svc = drive(small_config(), batches)
    drv = svc.driver
    seen_rep = seen_member = False
    for obj in np.asarray(drv.obj_of_row):
        if obj < 0:
            continue
        q = svc.query(int(obj))
        assert q["in_window"] and q["subtrajs"]
        for sub in q["subtrajs"]:
            assert sub["t_end"] >= sub["t_start"]
            if sub["is_rep"]:
                seen_rep = True
                assert sub["cluster"]["rep_slot"] == sub["slot"]
            elif sub["cluster"] is not None:
                seen_member = True
                assert sub["cluster"]["rep_obj"] >= 0
    assert seen_rep and seen_member
    assert not svc.query(99999)["in_window"]


# ------------------------------------------------------ launcher exit codes

def run_launcher(*flags):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.run_dsc", "--stream",
         "--n-trajs", "12", "--stream-batch-size", "48", *flags],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.slow
def test_launcher_stream_exit_codes(tmp_path):
    poison = tmp_path / "poison.json"
    poison.write_text(json.dumps({"stream_poison": [[1, 3]]}))
    ok = run_launcher()
    assert ok.returncode == EXIT_CODES["ok"], ok.stderr[-2000:]
    po = run_launcher("--on-dirty", "fail", "--fault-plan", str(poison))
    assert po.returncode == EXIT_CODES["poison"] == 7, po.stderr[-2000:]
    bp = run_launcher("--backpressure", "block", "--queue-cap", "10")
    assert bp.returncode == EXIT_CODES["backpressure"] == 8, \
        bp.stderr[-2000:]


@pytest.mark.slow
def test_launcher_stream_resume_roundtrip(tmp_path):
    crash = tmp_path / "crash.json"
    crash.write_text(json.dumps({"crash_at_advance": 3}))
    ck = str(tmp_path / "svc")
    first = run_launcher("--resume-dir", ck, "--fault-plan", str(crash))
    assert first.returncode == EXIT_CODES["injected_crash"] == 6
    second = run_launcher("--resume-dir", ck)
    assert second.returncode == 0, second.stderr[-2000:]
    assert "resumed stream service" in second.stderr
    events = read_telemetry(os.path.join(ck, "telemetry.jsonl"))
    assert any(e["event"] == "window_advanced" for e in events)
