"""EnginePlan surface + autotuner: serialization, the one-surface rule
at every entry point, sweep determinism, and bit-identity rejection."""
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.plan import EnginePlan
from repro.tune.autotune import (PlanStore, plan_cache_key, shape_bucket,
                                 sweep, tune_cluster_tiles, tune_join)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --------------------------------------------------------------- the plan


def test_plan_json_round_trip(tmp_path):
    plan = EnginePlan(mode="fused", fused_rows=4, fused_bc=8, fused_bm=32,
                      sim_mode="topk", sim_topk=16, sim_panel=64,
                      cluster_use_kernel=True, cluster_bu=16, cluster_bs=64)
    assert EnginePlan.from_json(plan.to_json()) == plan
    p = tmp_path / "plan.json"
    plan.save(p)
    assert EnginePlan.load(p) == plan
    # stored JSON is plain field->value, no nesting
    assert json.loads(p.read_text())["fused_bm"] == 32


def test_plan_from_dict_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown EnginePlan fields"):
        EnginePlan.from_dict({"mode": "fused", "warp_speed": 9})


def test_plan_validation_keeps_legacy_error_strings():
    with pytest.raises(ValueError, match="unknown mode 'stream'"):
        EnginePlan(mode="stream").validate()
    with pytest.raises(ValueError, match="unknown cluster engine 'greedy'"):
        EnginePlan(cluster_engine="greedy").validate()
    with pytest.raises(ValueError, match="unknown sim_mode 'sparse'"):
        EnginePlan(sim_mode="sparse").validate()
    with pytest.raises(ValueError, match="sim_topk"):
        EnginePlan(sim_topk=0).validate()


def test_plan_is_hashable_jit_static():
    # one plan == one trace: the frozen dataclass must hash stably and
    # compare equal across reconstruction
    a = EnginePlan(mode="fused", sim_topk=16)
    b = EnginePlan.from_dict(a.to_dict())
    assert hash(a) == hash(b) and a == b
    assert a.replace(sim_topk=32) != a


def test_fused_tiles_collapse_to_none_at_defaults():
    # default fused fields -> None so default plans keep the pre-plan jit
    # cache keys (no retrace on upgrade)
    assert EnginePlan().fused_tiles is None
    assert EnginePlan(fused_bm=32).fused_tiles == (None, 128, 32)
    assert EnginePlan().cluster_tiles == (8, 128)


def _entry_point_call(entry, batch, params):
    """``(fn, args)`` for one public entry point, on a 1x1 CPU mesh where
    it needs one."""
    from repro.core import distributed, dsc
    from repro.core.partitioning import partition_batch
    from repro.run import resilient
    if entry in ("run_dsc", "run_resilient"):
        mod = dsc if entry == "run_dsc" else resilient
        return getattr(mod, entry), (batch, params)
    mesh = jax.make_mesh((1, 1), ("part", "model"))
    mod = (resilient if entry == "run_resilient_distributed"
           else distributed)
    return getattr(mod, entry), (partition_batch(batch, 1), params, mesh)


@pytest.mark.parametrize("entry", [
    "run_dsc", "build_dsc_program", "run_dsc_distributed",
    "run_resilient", "run_resilient_distributed"])
def test_entry_points_reject_per_stage_keywords(entry, fig1, fig1_params):
    # the plan is the only engine surface: a per-stage keyword fails the
    # call's binding, before anything is traced or compiled
    fn, args = _entry_point_call(entry, fig1[0], fig1_params)
    with pytest.raises(TypeError, match="unexpected keyword.*'mode'"):
        fn(*args, mode="fused")


# ----------------------------------------------------- cache keys + store


def test_shape_bucket_and_cache_key():
    assert shape_bucket(T=24, M=96) == "M128-T32"
    assert shape_bucket(S=256) == shape_bucket(S=129) == "S256"
    assert shape_bucket(S=1) == "S1"
    key = plan_cache_key("join", "M128-T32", backend="cpu",
                         jax_version="0.4.37")
    assert key == "join|M128-T32|cpu|jax0.4.37"


def test_plan_store_round_trip(tmp_path):
    path = tmp_path / "plans.json"
    store = PlanStore(str(path))
    res = _run_fixed_sweep(store=store)
    store.save()
    again = PlanStore(str(path))
    got = again.get("unit", res.bucket, backend=jax.default_backend(),
                    jax_version=jax.__version__)
    assert got == res.winner.plan


# ------------------------------------------------------------- the sweep


def _fake_measure(sizes, walls):
    """Injectable measure: real (tiny) HLO per candidate so the buffer
    stats are exercised, candidate-keyed wall-clock, no compile per call
    beyond the tiny identity program."""
    def measure(plan):
        n = sizes[plan.cluster_bs]
        x = jnp.zeros((n,), jnp.float32)
        hlo = jax.jit(lambda v: v + 1.0).lower(x).compile().as_text()
        return plan.cluster_bs, walls[plan.cluster_bs], hlo
    return measure


_CANDS = [EnginePlan(),                       # default: bs=128
          EnginePlan(cluster_bs=64),
          EnginePlan(cluster_bs=32)]
_SIZES = {128: 1024, 64: 512, 32: 256}        # interface bytes = 4n
_WALLS = {128: 3e-3, 64: 2e-3, 32: 1e-3}


def _run_fixed_sweep(verify=None, store=None):
    return sweep("unit", "S256", _CANDS,
                 _fake_measure(_SIZES, _WALLS),
                 verify or (lambda out, plan: True), store=store)


def test_sweep_deterministic_on_fixed_candidates():
    a = _run_fixed_sweep()
    b = _run_fixed_sweep()
    assert a.winner.plan == b.winner.plan == EnginePlan(cluster_bs=32)
    assert [c.plan for c in a.candidates] == [c.plan for c in b.candidates]
    assert ([c.peak_interface_bytes for c in a.candidates]
            == [c.peak_interface_bytes for c in b.candidates])
    # candidate 0 is the default plan; the winner can't be worse on the
    # primary key
    assert a.default.plan == EnginePlan()
    assert a.winner.peak_interface_bytes <= a.default.peak_interface_bytes


def test_sweep_rejects_bit_unidentical_candidate():
    # the cheapest candidate (bs=32) fails verification -> the sweep must
    # NOT pick it, even though it wins on every ranking key
    res = _run_fixed_sweep(
        verify=lambda out, plan: plan.cluster_bs != 32)
    rejected = [c for c in res.candidates if not c.verified]
    assert len(rejected) == 1 and rejected[0].plan.cluster_bs == 32
    assert "not bit-identical" in rejected[0].note
    assert res.winner.plan == EnginePlan(cluster_bs=64)


def test_sweep_raises_when_nothing_verifies():
    with pytest.raises(RuntimeError, match="no candidate survived"):
        _run_fixed_sweep(verify=lambda out, plan: False)


def test_sweep_survives_a_failing_measure():
    def measure(plan):
        if plan.cluster_bs == 64:
            raise ValueError("invalid geometry")
        return _fake_measure(_SIZES, _WALLS)(plan)
    res = sweep("unit", "S256", _CANDS, measure, lambda o, p: True)
    failed = [c for c in res.candidates if "measure failed" in c.note]
    assert len(failed) == 1 and not failed[0].verified
    assert res.winner.plan == EnginePlan(cluster_bs=32)


# -------------------------------------------------- real stage sweeps


def _tiny_cluster_instance(S=32, seed=0):
    from repro.core.types import SubtrajTable
    rng = np.random.default_rng(seed)
    raw = rng.uniform(0, 1, (S, S)).astype(np.float32)
    sim = np.maximum(raw, raw.T) * (rng.uniform(0, 1, (S, S)) > 0.7)
    np.fill_diagonal(sim, 0.0)
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.asarray(rng.uniform(0, 5, S).astype(np.float32)),
        card=jnp.ones(S, jnp.int32), valid=jnp.ones(S, bool),
        traj_row=jnp.arange(S, dtype=jnp.int32))
    return jnp.asarray(np.maximum(sim, sim.T)), table


def test_tune_cluster_tiles_verifies_against_jnp_oracle():
    from repro.core.types import DSCParams
    sim, table = _tiny_cluster_instance()
    res = tune_cluster_tiles(sim, table,
                             DSCParams(alpha_sigma=0.0, k_sigma=0.0),
                             candidates=[EnginePlan(),
                                         EnginePlan(cluster_use_kernel=True,
                                                    cluster_bu=8,
                                                    cluster_bs=16)])
    assert all(c.verified for c in res.candidates)
    assert res.winner.peak_interface_bytes <= res.default.peak_interface_bytes
    assert res.bucket == "S32"


def test_tune_join_rejects_and_accepts_end_to_end(fig1, fig1_params):
    # two candidates: the materializing default and one fused geometry —
    # both must pass label verification; the winner must not regress the
    # interface-bytes key (candidate 0 is the default)
    fig1, _ = fig1
    res = tune_join(fig1, fig1_params,
                    candidates=[EnginePlan(),
                                EnginePlan(mode="fused", fused_rows=2,
                                           fused_bc=8, fused_bm=16)])
    assert all(c.verified for c in res.candidates)
    assert res.default.plan == EnginePlan()
    assert res.winner.peak_interface_bytes <= res.default.peak_interface_bytes
    # the audit record carries the roofline position when benchmarks/ is
    # importable (repo-root pytest runs)
    if res.winner.roofline is not None:
        assert res.winner.roofline["dominant"] in ("compute", "memory",
                                                   "collective")


# ------------------------------------------------------------- docs sync


def test_readme_cli_table_in_sync():
    from repro.launch.run_dsc import check_readme_cli_table
    readme = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")
    diff = check_readme_cli_table(readme)
    assert not diff, ("README CLI table out of sync; regenerate with "
                      "--print-cli-table:\n" + "\n".join(diff))


def test_launcher_rejects_plan_plus_legacy_flag(tmp_path):
    # --plan JSON is the launcher's one engine surface: it resolves to the
    # saved plan, no flag means EnginePlan(), and the removed per-stage
    # flags are refused by the parser
    from repro.launch.run_dsc import build_parser, plan_from_args
    p = tmp_path / "plan.json"
    EnginePlan(mode="fused", sim_mode="topk", sim_topk=16).save(p)
    ap = build_parser()
    assert plan_from_args(ap.parse_args(["--plan", str(p)])) == EnginePlan(
        mode="fused", sim_mode="topk", sim_topk=16)
    assert plan_from_args(ap.parse_args([])) == EnginePlan()
    with pytest.raises(SystemExit):
        ap.parse_args(["--plan", str(p), "--sim-mode", "topk"])
    with pytest.raises(SystemExit):
        ap.parse_args(["--sim-mode", "topk"])
