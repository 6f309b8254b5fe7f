"""DSC launcher: the paper's pipeline end-to-end on (synthetic) data.

``python -m repro.launch.run_dsc --config dsc_synth [--distributed P]``

Engine configuration rides on one surface: ``--plan PLAN.json``, an
:class:`repro.core.plan.EnginePlan` (DESIGN.md §9; write one with
``EnginePlan(...).save(path)`` or take a tuned one from the autotuner's
plan store).  Without it the launcher runs ``EnginePlan()``.

The README's CLI reference table is generated from this parser
(``--print-cli-table``) and checked for drift in CI (``--check-readme``).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_dsc_config
from repro.core.dsc import cluster_summary, run_dsc
from repro.core.partitioning import partition_batch
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams
from repro.data.synthetic import (ais_like, default_dsc_params_for,
                                  figure1_scenario)
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import get_logger

log = get_logger("run_dsc")

# README markers the generated CLI table lives between (docs-consistency CI)
CLI_TABLE_BEGIN = "<!-- cli-table:begin (generated by --print-cli-table) -->"
CLI_TABLE_END = "<!-- cli-table:end -->"


def make_dataset(name: str, n_trajs: int, max_points: int, seed: int = 0):
    if name == "dsc_synth":
        per = max(1, n_trajs // 6)
        return figure1_scenario(n_per_route=per, points_per_leg=32,
                                seed=seed)[0]
    return ais_like(n_vessels=n_trajs, max_points=max_points,
                    n_lanes=8, seed=seed)[0]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.run_dsc",
        description="Run the DSC pipeline (single host or distributed).")
    ap.add_argument("--config", default="dsc_synth",
                    help="dataset/config name from the registry")
    ap.add_argument("--n-trajs", type=int, default=None,
                    help="trajectory count override (default: config)")
    ap.add_argument("--distributed", type=int, default=0,
                    help="number of temporal partitions (0 = single host)")
    ap.add_argument("--model-par", type=int, default=1,
                    help="model-axis width (candidate-column sharding)")
    ap.add_argument("--plan", default=None, metavar="PLAN.json",
                    help="EnginePlan JSON: the one surface owning every "
                         "per-stage engine and tile choice (DESIGN.md "
                         "section 9; default: EnginePlan())")
    ap.add_argument("--segmentation", default=None,
                    help="segmentation algorithm override (tsa1 or tsa2)")
    ap.add_argument("--seed", type=int, default=0,
                    help="dataset RNG seed")
    ap.add_argument("--resume-dir", default=None, metavar="DIR",
                    help="run through the resilient stage runner, "
                         "checkpointing each stage boundary under DIR and "
                         "resuming from the first incomplete stage on "
                         "restart (bit-identical to a straight-through "
                         "run; DESIGN.md section 10)")
    ap.add_argument("--on-overflow", default=None,
                    choices=["raise", "widen", "degrade"],
                    help="top-K overflow policy: fail loudly, double K "
                         "and re-run from the similarity stage, or accept "
                         "the truncated lists and record the certificate "
                         "(default: widen; implies the resilient runner)")
    ap.add_argument("--on-corruption", default="fallback",
                    choices=["fallback", "fail"],
                    help="damaged-checkpoint policy for --resume-dir: "
                         "discard the bad step and restore the newest "
                         "intact one, or exit with the corruption code")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="bounded exponential-backoff retries per stage "
                         "before giving up (resilient runner)")
    ap.add_argument("--fault-plan", default=None, metavar="FAULTS.json",
                    help="FaultPlan JSON scripting deterministic failures "
                         "(crash/transient/corruption/slowdown) at stage "
                         "boundaries — chaos testing; implies the "
                         "resilient runner")
    ap.add_argument("--elastic-resume", action="store_true",
                    help="allow --resume-dir checkpoints written at a "
                         "different partition count: join/segment state "
                         "folds to global point space and re-cuts for "
                         "this mesh, later stages rewind to the segment "
                         "boundary — bit-identical to a straight-through "
                         "run at the new P (distributed only; DESIGN.md "
                         "section 11)")
    ap.add_argument("--rebalance", default=None,
                    choices=["off", "suggest", "apply"],
                    help="straggler-flag policy (RebalancePolicy): "
                         "ignore flags, emit slowdown-weighted "
                         "rebalance-edge suggestions to telemetry "
                         "(default), or apply the re-cut at the next "
                         "join/segment boundary and checkpoint the "
                         "repartitioned state; implies the resilient "
                         "runner")
    ap.add_argument("--sync-saves", action="store_true",
                    help="write stage checkpoints synchronously instead "
                         "of overlapping the save with the next stage "
                         "(escape hatch; results are identical either "
                         "way)")
    ap.add_argument("--stream", action="store_true",
                    help="run the windowed streaming service instead of a "
                         "batch pipeline: the dataset is replayed as "
                         "point-record submissions through ingest "
                         "validation, the bounded-lateness watermark and "
                         "incremental window advances (DESIGN.md section "
                         "13); --resume-dir snapshots/resumes service "
                         "state, --fault-plan scripts stream chaos")
    ap.add_argument("--stream-batch-size", type=int, default=64,
                    help="records per submission batch when replaying the "
                         "dataset as a stream")
    ap.add_argument("--stream-order", default="time",
                    choices=["time", "traj"],
                    help="record replay order: global event-time order "
                         "(realistic feed) or trajectory-major (watermark "
                         "worst case)")
    ap.add_argument("--pump-every", type=int, default=1,
                    help="window advances once per this many submissions")
    ap.add_argument("--allowed-lateness", type=float, default=None,
                    help="bounded-lateness watermark slack in event-time "
                         "seconds (default: 2x the mean sampling interval)")
    ap.add_argument("--horizon", type=float, default=None,
                    help="active-window retention in event-time seconds; "
                         "older points are evicted at each advance "
                         "(default: the dataset's full time span)")
    ap.add_argument("--queue-cap", type=int, default=4096,
                    help="staging-queue record capacity between "
                         "submissions and the next window advance")
    ap.add_argument("--backpressure", default="shed_oldest",
                    choices=["shed_oldest", "block"],
                    help="staging-queue overflow policy: shed (and count) "
                         "the oldest staged records, or raise "
                         "BackpressureOverflow (exit code 8)")
    ap.add_argument("--on-dirty", default="repair",
                    choices=["repair", "drop", "fail"],
                    help="dirty-record policy: repair what is mechanically "
                         "fixable and quarantine the rest, quarantine "
                         "everything, or raise PoisonRecord on first dirt "
                         "(exit code 7)")
    ap.add_argument("--max-speed", type=float, default=None,
                    help="GPS-teleport gate in units/second against the "
                         "object's last admitted fix (default: disabled)")
    ap.add_argument("--snapshot-every", type=int, default=1,
                    help="full-state snapshot cadence in window advances "
                         "(0 disables; needs --resume-dir)")
    ap.add_argument("--stream-k", type=int, default=8,
                    help="standing neighbor-list width K of the streaming "
                         "service (lists keep K+1 for the certificate)")
    ap.add_argument("--alpha-abs", type=float, default=0.1,
                    help="absolute similarity threshold for streaming "
                         "(sigma-relative thresholds would drift with the "
                         "window)")
    ap.add_argument("--k-abs", type=float, default=0.0,
                    help="absolute voting threshold for streaming")
    ap.add_argument("--print-cli-table", action="store_true",
                    help="print the generated markdown CLI reference "
                         "table (the one embedded in the README) and exit")
    ap.add_argument("--check-readme", default=None, nargs="?",
                    const="README.md", metavar="README",
                    help="verify the README's embedded CLI table matches "
                         "this parser and exit nonzero on drift (CI "
                         "docs-consistency gate)")
    return ap


def cli_reference_table() -> str:
    """Markdown CLI reference generated from :func:`build_parser`.

    One row per flag, straight from the parser's own metadata — the
    README embeds this between ``cli-table`` markers and CI regenerates
    it, so the documented surface cannot drift from the implemented one.
    """
    ap = build_parser()
    lines = ["| flag | default | description |",
             "| --- | --- | --- |"]
    for a in ap._actions:
        if "--help" in a.option_strings or not a.option_strings:
            continue
        flag = " ".join(f"`{s}`" for s in a.option_strings)
        if a.metavar:
            flag += f" `{a.metavar}`"
        elif a.choices:
            flag += " `{" + ",".join(map(str, a.choices)) + "}`"
        elif a.type is int:
            flag += " `N`"
        elif not isinstance(a, (argparse._StoreTrueAction,
                                argparse._StoreConstAction)):
            flag += " `V`"
        if isinstance(a, argparse._StoreTrueAction):
            default = "off"
        elif a.default is None:
            default = "—"
        else:
            default = f"`{a.default}`"
        help_ = " ".join((a.help or "").split())
        lines.append(f"| {flag} | {default} | {help_} |")
    return "\n".join(lines) + "\n"


def readme_cli_table(readme_path: str) -> str:
    """Extract the embedded CLI table from a README (between markers)."""
    with open(readme_path) as f:
        text = f.read()
    try:
        _, rest = text.split(CLI_TABLE_BEGIN, 1)
        body, _ = rest.split(CLI_TABLE_END, 1)
    except ValueError:
        raise ValueError(
            f"{readme_path} is missing the CLI-table markers "
            f"{CLI_TABLE_BEGIN!r} / {CLI_TABLE_END!r}") from None
    return body.strip("\n") + "\n"


def check_readme_cli_table(readme_path: str) -> list[str]:
    """Diff lines between the README's table and the generated one
    (empty list = in sync).  The CI docs-consistency job and the tier-1
    sync test both call this."""
    import difflib
    want = cli_reference_table().splitlines()
    have = readme_cli_table(readme_path).splitlines()
    return list(difflib.unified_diff(have, want, fromfile=readme_path,
                                     tofile="--print-cli-table", lineterm=""))


def plan_from_args(args) -> EnginePlan:
    """The run's plan: the ``--plan`` JSON, else ``EnginePlan()``."""
    return EnginePlan() if args.plan is None else EnginePlan.load(args.plan)


def _exit_code_for(exc) -> int | None:
    """Map a failure to its documented process exit code (None = not a
    classified failure; let it propagate as a plain error)."""
    from repro.run import (EXIT_CODES, CheckpointCorruption, InjectedCrash,
                           RetriesExhausted)
    from repro.run.resilient import OverflowViolation
    if isinstance(exc, InjectedCrash):
        return EXIT_CODES["injected_crash"]
    if isinstance(exc, RetriesExhausted):
        return EXIT_CODES["retries_exhausted"]
    if isinstance(exc, CheckpointCorruption):
        return EXIT_CODES["corruption"]
    if isinstance(exc, OverflowViolation):
        return EXIT_CODES["overflow"]
    if isinstance(exc, RuntimeError) and "sim_topk" in str(exc):
        return EXIT_CODES["overflow"]  # monolithic runners' raise path
    from repro.stream import (BackpressureOverflow, PoisonRecord,
                              WatermarkStall)
    if isinstance(exc, PoisonRecord):
        return EXIT_CODES["poison"]
    if isinstance(exc, (BackpressureOverflow, WatermarkStall)):
        return EXIT_CODES["backpressure"]
    return None


def dataset_and_params(args):
    """``(batch, params, mean_dt)`` for parsed launcher ``args``: the
    config's generated dataset (``--n-trajs`` overrides its trajectory
    count) and the DSC parameters derived from it."""
    rc = get_dsc_config(args.config)
    n_trajs = args.n_trajs or rc.n_trajs
    batch = make_dataset(args.config, n_trajs, rc.max_points, args.seed)
    diam, mean_dt = default_dsc_params_for(batch)
    params = DSCParams(
        eps_sp=0.15 * diam if args.config != "dsc_synth" else 0.42,
        eps_t=1.0 * mean_dt, delta_t=rc.delta_t,
        w=min(rc.w, 6 if args.config == "dsc_synth" else rc.w),
        tau=0.15 if args.config == "dsc_synth" else rc.tau,
        alpha_sigma=-1.0, k_sigma=-1.0,
        max_subtrajs_per_traj=rc.max_subtrajs,
        segmentation=args.segmentation or ("tsa2" if args.config ==
                                           "dsc_synth" else rc.segmentation))
    return batch, params, mean_dt


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.print_cli_table:
        print(cli_reference_table(), end="")
        return None
    if args.check_readme is not None:
        diff = check_readme_cli_table(args.check_readme)
        if diff:
            raise SystemExit(
                "README CLI table is out of sync with the parser — "
                "regenerate it with --print-cli-table:\n" + "\n".join(diff))
        print(f"{args.check_readme}: CLI table in sync")
        return None
    plan = plan_from_args(args)
    enable_compile_cache()
    batch, params, mean_dt = dataset_and_params(args)

    if args.stream:
        if args.distributed:
            ap.error("--stream is a single-host service "
                     "(no --distributed)")
        try:
            return _run_stream(args, batch, mean_dt)
        except Exception as e:  # noqa: BLE001 - classified -> exit code
            code = _exit_code_for(e)
            if code is None:
                raise
            log.error("%s: %s (exit code %d)", type(e).__name__, e, code)
            raise SystemExit(code) from e

    resilient = (args.resume_dir is not None or args.fault_plan is not None
                 or args.on_overflow is not None
                 or args.rebalance is not None)
    if args.elastic_resume and not (resilient and args.distributed):
        ap.error("--elastic-resume needs --resume-dir and --distributed "
                 "(elastic adaptation is a distributed-checkpoint "
                 "feature)")
    t0 = time.time()
    try:
        if args.distributed:
            P = args.distributed
            if len(jax.devices()) < P * args.model_par:
                raise SystemExit(
                    f"need {P * args.model_par} devices; run under "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{P * args.model_par}")
            mesh = jax.make_mesh((P, args.model_par), ("part", "model"))
            parts = partition_batch(batch, P)
            # the distributed program has no in-graph K widening: keep
            # the default K of 32 explicit in the plan
            if plan.sim_mode == "topk" and plan.sim_topk is None:
                plan = plan.replace(sim_topk=32)
            if resilient:
                out = _run_resilient(args, parts, params, plan, mesh=mesh)
            else:
                from repro.core.distributed import run_dsc_distributed
                out = run_dsc_distributed(parts, params, mesh, plan=plan)
            res = out.result
            n_rep = int(np.asarray(res.is_rep).sum())
            n_out = int(np.asarray(res.is_outlier).sum())
            n_mem = int(((np.asarray(res.member_of) >= 0)
                         & ~np.asarray(res.is_rep)).sum())
            log.info("distributed DSC (%d partitions x %d model): "
                     "%d clusters, %d members, %d outliers in %.2fs",
                     P, args.model_par, n_rep, n_mem, n_out,
                     time.time() - t0)
        else:
            if resilient:
                out = _run_resilient(args, batch, params, plan)
            else:
                out = run_dsc(batch, params, plan=plan)
            s = cluster_summary(out)
            log.info("DSC: %d clusters, %d outliers, RMSE %.4f, SSCR "
                     "%.2f in %.2fs", s["num_clusters"], len(s["outliers"]),
                     s["rmse"], s["sscr"], time.time() - t0)
            for rep, members in sorted(s["clusters"].items(),
                                       key=lambda kv: -len(kv[1]))[:8]:
                log.info("  cluster rep=%d size=%d", rep, len(members))
    except Exception as e:  # noqa: BLE001 - classified failure -> exit code
        code = _exit_code_for(e)
        if code is None:
            raise
        log.error("%s: %s (exit code %d)", type(e).__name__, e, code)
        raise SystemExit(code) from e
    return out


def _run_stream(args, batch, mean_dt):
    """Replay the dataset as a record stream through the windowed
    incremental service (DESIGN.md §13)."""
    import time as _time
    from pathlib import Path

    from repro.data.synthetic import stream_records
    from repro.run import FaultInjector, FaultPlan, Telemetry
    from repro.serve.stream import StreamService
    from repro.stream import StreamConfig

    t = np.asarray(batch.t)[np.asarray(batch.valid)]
    span = float(t.max() - t.min()) if t.size else 1.0
    lateness = (args.allowed_lateness if args.allowed_lateness is not None
                else 2.0 * mean_dt)
    horizon = (args.horizon if args.horizon is not None
               else max(span, lateness))
    cfg = StreamConfig(
        t_cap=batch.num_trajs, m_cap=batch.max_points,
        eps_sp=0.42 if args.config == "dsc_synth" else 0.3,
        eps_t=1.0 * mean_dt, alpha_abs=args.alpha_abs, k_abs=args.k_abs,
        allowed_lateness=lateness, horizon=horizon,
        k=args.stream_k, w=min(6, batch.max_points // 2),
        queue_cap=args.queue_cap, backpressure=args.backpressure,
        on_dirty=args.on_dirty, max_speed=args.max_speed,
        snapshot_every=args.snapshot_every if args.resume_dir else 0)
    fault_plan = (FaultPlan.load(args.fault_plan)
                  if args.fault_plan else None)
    injector = FaultInjector(fault_plan) if fault_plan else None
    telemetry = None
    if args.resume_dir:
        Path(args.resume_dir).mkdir(parents=True, exist_ok=True)
        telemetry = Telemetry(Path(args.resume_dir) / "telemetry.jsonl",
                              _time.time)
    svc = StreamService(cfg, checkpoint_dir=args.resume_dir,
                        telemetry=telemetry, injector=injector)
    if svc.resumed:
        log.info("resumed stream service at advance %d (cursor %d)",
                 svc.driver.advance_count, svc.driver.cursor)
    batches = stream_records(batch, batch_size=args.stream_batch_size,
                             order=args.stream_order)
    t0 = time.time()
    stats = svc.run(batches, pump_every=args.pump_every)
    acc = svc.accounting()
    log.info("stream: %d advances, %d objects, %d points in window, "
             "%d reps, %d outliers in %.2fs", stats["advances"],
             stats["objects"], stats["points"], stats["reps"],
             stats["outliers"], time.time() - t0)
    log.info("accounting: submitted=%d inserted=%d quarantined=%d "
             "late=%d shed=%d balanced=%s", acc["submitted"],
             acc["inserted"], acc["quarantined"], acc["late_dropped"],
             acc["shed_queue"] + acc["shed_capacity"], acc["balanced"])
    if not acc["balanced"]:
        raise RuntimeError(f"stream accounting imbalance: {acc}")
    return stats


def _run_resilient(args, data, params, plan, mesh=None):
    """Route a launcher invocation through the resilient stage runner."""
    from repro.run import (FaultPlan, RebalancePolicy, run_resilient,
                           run_resilient_distributed)
    fault_plan = (FaultPlan.load(args.fault_plan)
                  if args.fault_plan else None)
    rebalance = (RebalancePolicy(mode=args.rebalance)
                 if args.rebalance is not None else None)
    kw = dict(plan=plan, checkpoint_dir=args.resume_dir,
              on_overflow=args.on_overflow or "widen",
              on_corruption=args.on_corruption,
              fault_plan=fault_plan, max_retries=args.max_retries,
              rebalance=rebalance, sync_saves=args.sync_saves)
    if mesh is None:
        res = run_resilient(data, params, **kw)
    else:
        res = run_resilient_distributed(data, params, mesh,
                                        elastic_resume=args.elastic_resume,
                                        **kw)
    log.info("resilient run: resumed_from=%s widen_count=%d "
             "rebalance_count=%d fallback_steps=%s", res.resumed_from,
             res.widen_count, res.rebalance_count, res.fallback_steps)
    return res.output


if __name__ == "__main__":
    main()
