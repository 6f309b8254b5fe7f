"""Smoke run of DSC on a TPU: the quickest proof that the system starts.

    python chip_smoke.py             # one chip: batch, parity and stream
    python chip_smoke.py --chips 4   # the (part, model) mesh on four chips

One chip (the default) runs three phases in this one process:

1. batch: the launcher (``repro.launch.run_dsc.main``) on the
   ``dsc_brest`` deployment's widths (M = 128 points per trajectory, 8
   sub-slots, w = 20) at T = 2048 trajectories, once with TSA1 and once
   with TSA2, under the kernel plan (Pallas join, index, segmentation and
   clustering kernels).  The stage programs the dispatcher ran are
   recorded (``repro.utils.programs``); each must contain
   ``tpu_custom_call``, so no kernel ran in interpret mode.  Labels must
   be consistent.
2. parity: at T = 256 the kernel plan's labels equal the plain plan's
   (materialize join in jnp, dense similarity, sequential clustering)
   label for label, for TSA1 and TSA2.
3. stream: ``StreamService`` advances its window over an AIS-like replay
   (t_cap = 256, m_cap = 64) and must end with balanced accounting.

``--chips 4`` runs only the mesh phase: ``run_dsc_distributed`` on a
(part=2, model=2) mesh under the barrier and the ring schedules, each
compared label for label with (part=2, model=1) barrier on two chips.

Timings, cluster counts and device memory go to stdout; the last line is
one JSON object naming the device.  Without a TPU, or without the
``repro`` sources next to this script, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
BATCH_T, PARITY_T = 2048, 256


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def kernel_plan():
    from repro.core.plan import EnginePlan
    # (512, 512) clustering tiles: 1 MiB blocks, 1024 grid steps per round
    # over the [S, S] matrix instead of 262,144 at the (8, 128) default
    return EnginePlan(use_kernel=True, use_index=True,
                      cluster_use_kernel=True, seg_use_kernel=True,
                      cluster_bu=512, cluster_bs=512)


def launcher_args(n: int, seg: str, extra=()):
    return ["--config", "dsc_brest", "--n-trajs", str(n), "--seed",
            str(SEED), "--segmentation", seg, *extra]


def labels(out):
    import numpy as np
    r = out.result
    return {f: np.asarray(getattr(r, f))
            for f in ("member_of", "is_rep", "is_outlier")}


def check_labels(out, tag: str):
    """Labels are finite and consistent with ClusteringResult's states."""
    import numpy as np
    lab = labels(out)
    member, rep, outl = lab["member_of"], lab["is_rep"], lab["is_outlier"]
    valid = np.asarray(out.table.valid)
    S = member.shape[0]
    check(np.isfinite(float(out.sscr)) and np.isfinite(float(out.rmse)),
          f"{tag}: non-finite quality metrics")
    check(((member >= -1) & (member < S)).all(), f"{tag}: member_of range")
    check((member[rep] == np.nonzero(rep)[0]).all(),
          f"{tag}: a representative is not its own cluster")
    check(not (rep & outl).any(), f"{tag}: a slot is rep and outlier")
    check(not ((rep | outl) & ~valid).any(), f"{tag}: an empty slot labelled")
    owned = (member >= 0) & ~rep
    check(rep[member[owned]].all(), f"{tag}: a member's owner is no rep")
    check(np.isfinite(np.asarray(out.result.member_sim)[owned]).all(),
          f"{tag}: a member's similarity is not finite")
    check((outl == (valid & (member < 0))).all(),
          f"{tag}: outliers disagree with member_of")
    return int(rep.sum()), int(owned.sum()), int(outl.sum())


def kernel_counts(programs):
    """Pallas kernels in each recorded stage program's compiled text (the
    compile is a cache hit: the same program just ran)."""
    return {name: low.compile().as_text().count(
                'custom_call_target="tpu_custom_call"')
            for name, low in programs}


def batch_phase(dev, plan_path: Path):
    import jax
    from repro.launch import run_dsc
    from repro.utils.programs import record_programs
    for seg in ("tsa1", "tsa2"):
        args = launcher_args(BATCH_T, seg, ["--plan", str(plan_path)])
        t0 = time.perf_counter()
        with record_programs() as programs:
            out = run_dsc.main(args)
        jax.block_until_ready(out.result.member_of)
        wall = time.perf_counter() - t0
        reps, members, outliers = check_labels(out, f"batch {seg}")
        check(reps > 0, f"batch {seg}: no cluster found")
        log(f"batch {seg}: T={BATCH_T} M=128 S={out.table.num_slots} "
            f"clusters={reps} members={members} outliers={outliers} "
            f"wall_s={wall:.2f} (generation, planning, compile and run) "
            f"peak_bytes_in_use={peak_bytes(dev)}")
        kernels = kernel_counts(programs)
        log(f"batch {seg}: tpu_custom_call per stage program {kernels}")
        # the pruned stjoin sweep; the tail's SP sweep, round scan and
        # claim-max (+ the Jaccard segmentation kernel under TSA2)
        want = {"_join_sweep": 1,
                "_run_dsc_from_join": 4 if seg == "tsa2" else 3}
        check(sorted(kernels) == sorted(want),
              f"batch {seg}: ran stage programs {sorted(kernels)}, "
              f"expected {sorted(want)}")
        for name, n in want.items():
            check(kernels[name] >= n,
                  f"batch {seg}: {name} program has {kernels[name]} "
                  f"Pallas kernels, expected at least {n}")
        del out


def parity_phase(dev, plan_path: Path):
    import numpy as np
    from repro.core.plan import EnginePlan
    from repro.launch import run_dsc
    plain_path = plan_path.with_name("sequential_plan.json")
    EnginePlan(cluster_engine="sequential").save(plain_path)
    for seg in ("tsa1", "tsa2"):
        t0 = time.perf_counter()
        got = run_dsc.main(launcher_args(PARITY_T, seg,
                                         ["--plan", str(plan_path)]))
        want = run_dsc.main(launcher_args(
            PARITY_T, seg, ["--plan", str(plain_path)]))
        a, b = labels(got), labels(want)
        same = {f: bool(np.array_equal(a[f], b[f])) for f in a}
        reps, _, _ = check_labels(got, f"parity {seg}")
        log(f"parity {seg}: T={PARITY_T} kernel plan vs plain plan "
            f"labels_equal={same} clusters={reps} "
            f"wall_s={time.perf_counter() - t0:.2f} "
            f"peak_bytes_in_use={peak_bytes(dev)}")
        check(all(same.values()),
              f"parity {seg}: kernel plan labels differ from the plain plan")


def stream_phase(dev):
    import numpy as np
    from repro.data.synthetic import (ais_like, default_dsc_params_for,
                                      stream_records)
    from repro.serve.stream import StreamService
    from repro.stream import StreamConfig
    batch, _ = ais_like(n_vessels=256, max_points=64, n_lanes=8, seed=SEED)
    _, mean_dt = default_dsc_params_for(batch)
    t = np.asarray(batch.t)[np.asarray(batch.valid)]
    cfg = StreamConfig(
        t_cap=256, m_cap=64, eps_sp=0.3, eps_t=mean_dt, alpha_abs=0.1,
        k_abs=0.0, allowed_lateness=2.0 * mean_dt,
        horizon=float(t.max() - t.min()), k=8, w=6)
    svc = StreamService(cfg)
    t0 = time.perf_counter()
    stats = svc.run(stream_records(batch, batch_size=512), max_batches=8)
    acc = svc.accounting()
    log(f"stream: advances={stats['advances']} objects={stats['objects']} "
        f"points={stats['points']} reps={stats['reps']} "
        f"outliers={stats['outliers']} submitted={acc['submitted']} "
        f"balanced={acc['balanced']} "
        f"wall_s={time.perf_counter() - t0:.2f} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    check(stats["advances"] >= 3, "stream: fewer than 3 window advances")
    check(acc["balanced"], f"stream: accounting imbalance {acc}")


def mesh_phase(devs, n_trajs: int = BATCH_T):
    """(part=2, model=2) barrier and ring vs (part=2, model=1) barrier.

    The similarity is dense: each partition's ``[S, S]`` block is 1.07 GB
    at T = 2048, and a top-K list of the default width would truncate
    alpha-edges on thousands of rows of this data (``run_dsc_distributed``
    refuses such a run rather than return inexact labels)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import run_dsc_distributed
    from repro.core.partitioning import partition_batch
    from repro.launch.run_dsc import build_parser, dataset_and_params

    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    batch, params, _ = dataset_and_params(build_parser().parse_args(
        launcher_args(n_trajs, "tsa1")))
    parts = partition_batch(batch, 2)
    base = kernel_plan().replace(mode="fused", use_kernel=False,
                                 use_index=False)
    runs = {
        "p2m1_barrier": (np.array(devs[:2]).reshape(2, 1), base),
        "p2m2_barrier": (np.array(devs[:4]).reshape(2, 2), base),
        # the ring similarity exchange streams the all-gathered columns
        "p2m2_ring": (np.array(devs[:4]).reshape(2, 2),
                      base.replace(halo_stream="ring",
                                   sim_strategy="allgather",
                                   sim_exchange="ring")),
    }
    got = {}
    for name, (grid, plan) in runs.items():
        mesh = Mesh(grid, ("part", "model"))
        t0 = time.perf_counter()
        out = run_dsc_distributed(parts, params, mesh, plan=plan)
        jax.block_until_ready(out.result.member_of)
        got[name] = labels(out)
        used = {str(d.id): int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", -1)) for d in devs[:4]}
        log(f"mesh {name}: clusters={int(got[name]['is_rep'].sum())} "
            f"wall_s={time.perf_counter() - t0:.2f} "
            f"peak_bytes_in_use_per_device={used}")
        check(got[name]["is_rep"].any(), f"mesh {name}: no cluster found")
    for name in ("p2m2_barrier", "p2m2_ring"):
        same = {f: bool(np.array_equal(got[name][f], got["p2m1_barrier"][f]))
                for f in got[name]}
        log(f"mesh {name} vs p2m1_barrier: labels_equal={same}")
        check(all(same.values()), f"mesh {name}: labels differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: batch, parity and stream phases on one chip; "
                         "4: only the (part, model) mesh phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro sources (src/repro) are not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    log(f"device: {dev.device_kind} x{len(devs)}; compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(devs)
    else:
        with tempfile.TemporaryDirectory() as tmp:
            plan_path = Path(tmp) / "kernel_plan.json"
            kernel_plan().save(plan_path)
            batch_phase(dev, plan_path)
            parity_phase(dev, plan_path)
        stream_phase(dev)
    log(f"total_wall_s={time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
