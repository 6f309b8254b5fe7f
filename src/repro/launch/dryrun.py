import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (no device allocation — ShapeDtypeStruct only):
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — XLA's flops/bytes (loop bodies counted 1x)
  * loop-corrected FLOPs + collective bytes (repro.launch.hlo_analysis)
and writes one JSON per cell under results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --dsc dsc_synth --mesh single
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import (ARCHITECTURES, DSC_CONFIGS, SHAPES,
                                    get_arch, get_dsc_config,
                                    shape_applicable)
from repro.distributed import partition
from repro.launch import hlo_analysis
from repro.launch.input_specs import abstract_train_state, input_specs
from repro.launch.mesh import dp_axes_of, make_dsc_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.serve.engine import decode_step, prefill_step
from repro.train.step import train_step
from repro.utils.logging import get_logger

log = get_logger("dryrun")
RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _mem_dict(mem) -> dict:
    keys = ["generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "host_generated_code_size_in_bytes",
            "host_argument_size_in_bytes", "host_output_size_in_bytes",
            "host_temp_size_in_bytes", "peak_memory_in_bytes"]
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_dict(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {k: float(v) for k, v in dict(cost).items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or "utilization" not in k)}


def run_cell(arch: str, shape: str, multi_pod: bool,
             save_hlo: bool = False, policy: str = "tp",
             moe_quant: bool = False, moe_cap: float = None,
             remat: bool = True, suffix: str = "") -> dict:
    import dataclasses as _dc
    cfg = get_arch(arch)
    if cfg.moe is not None and (moe_quant or moe_cap):
        moe = cfg.moe
        if moe_quant:
            moe = _dc.replace(moe, quantize_dispatch=True)
        if moe_cap:
            moe = _dc.replace(moe, capacity_factor=moe_cap)
        cfg = _dc.replace(cfg, moe=moe)
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape,
           "mesh": "multi" if multi_pod else "single",
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    if not ok:
        rec.update(status="SKIP", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes_of(mesh)
    if policy in ("dp_only", "dp_fsdp"):
        dp = dp + ("model",)
    spec = input_specs(arch, shape)
    kind = spec["kind"]
    ep = mesh.shape.get("model", 1)
    rec["policy"] = policy
    rec["moe_quant"] = moe_quant

    t0 = time.time()
    with mesh:
        if kind == "train":
            state = abstract_train_state(cfg, ep_degree=ep)
            pspecs = partition.param_specs(state.params, cfg, mesh,
                                           policy=policy)
            state_sh = partition.named(mesh, dataclasses.replace(
                state,
                params=pspecs,
                opt=dataclasses.replace(
                    state.opt, step=P(), mu=pspecs, nu=pspecs)))
            dspecs = partition.data_specs(
                cfg, mesh, kind=kind, global_batch=spec["batch"],
                seq_len=spec["seq"], policy=policy)
            tok_sh = NamedSharding(mesh, dspecs["tokens"])
            args = [state, spec["tokens"], spec["labels"]]
            in_sh = [state_sh, tok_sh, tok_sh]
            if "frontend" in spec:
                args.append(spec["frontend"])
                in_sh.append(NamedSharding(mesh, dspecs["frontend"]))

                def fn(st, tok, lab, fe):
                    return train_step(st, tok, lab, cfg,
                                      frontend_inputs=fe, mesh=mesh,
                                      dp_axes=dp, remat=remat)
            else:
                def fn(st, tok, lab):
                    return train_step(st, tok, lab, cfg, mesh=mesh,
                                      dp_axes=dp, remat=remat)
            jitted = jax.jit(fn, in_shardings=tuple(in_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(*args)
        else:
            params = jax.eval_shape(
                lambda: tf.init_model(jax.random.PRNGKey(0), cfg,
                                      ep_degree=ep))
            pspecs = partition.param_specs(params, cfg, mesh,
                                           policy=policy)
            params_sh = partition.named(mesh, pspecs)
            dspecs = partition.data_specs(
                cfg, mesh, kind=kind, global_batch=spec["batch"],
                seq_len=spec["seq"], policy=policy)
            tok_sh = NamedSharding(mesh, dspecs["tokens"])
            cache_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), dspecs["cache"],
                is_leaf=lambda x: isinstance(x, P))
            # align cache sharding tree with the abstract cache pytree
            cache_abs = spec["cache"]
            cache_sh_tree = {k: cache_sh[k] for k in cache_abs}
            if kind == "prefill":
                if "frontend" in spec:
                    def fn(p, tok, cache, fe):
                        return prefill_step(p, tok, cache, cfg,
                                            frontend_inputs=fe, mesh=mesh,
                                            dp_axes=dp)
                    jitted = jax.jit(
                        fn, in_shardings=(params_sh, tok_sh, cache_sh_tree,
                                          NamedSharding(
                                              mesh, dspecs["frontend"])),
                        donate_argnums=(2,))
                    lowered = jitted.lower(params, spec["tokens"],
                                           cache_abs, spec["frontend"])
                else:
                    def fn(p, tok, cache):
                        return prefill_step(p, tok, cache, cfg, mesh=mesh,
                                            dp_axes=dp)
                    jitted = jax.jit(
                        fn, in_shardings=(params_sh, tok_sh, cache_sh_tree),
                        donate_argnums=(2,))
                    lowered = jitted.lower(params, spec["tokens"], cache_abs)
            else:
                def fn(p, tok, cache, idx):
                    return decode_step(p, tok, cache, idx, cfg, mesh=mesh,
                                       dp_axes=dp)
                jitted = jax.jit(
                    fn, in_shardings=(params_sh, tok_sh, cache_sh_tree,
                                      NamedSharding(mesh, P())),
                    donate_argnums=(2,))
                lowered = jitted.lower(params, spec["tokens"], cache_abs,
                                       spec["index"])
        compiled = lowered.compile()

    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    corrected = hlo_analysis.analyze_hlo(text)
    rec.update(
        status="OK",
        compile_seconds=round(t1 - t0, 1),
        memory=_mem_dict(mem),
        cost=_cost_dict(cost),
        corrected_flops=corrected["flops"],
        hbm_traffic_bytes=corrected["hbm_traffic_bytes"],
        hbm_traffic_fused_bytes=corrected["hbm_traffic_fused_bytes"],
        collective_bytes=corrected["collective_bytes"],
        collectives=corrected["collectives"],
        num_whiles=corrected["num_whiles"],
        sharding_report=partition.report_sharding(
            state.params if kind == "train" else params, pspecs),
        devices=int(np.prod(list(mesh.shape.values()))),
        mesh_shape=dict(mesh.shape),
    )
    import gzip
    with gzip.open(
            RESULTS / f"{arch}_{shape}_{rec['mesh']}{suffix}.hlo.txt.gz",
            "wt") as fh:
        fh.write(text)
    del save_hlo
    print(f"[dryrun] {arch} x {shape} x {rec['mesh']}: "
          f"compile {rec['compile_seconds']}s, "
          f"temp {rec['memory'].get('temp_size_in_bytes', 0)/2**30:.2f} GiB, "
          f"flops {rec['corrected_flops']:.3e}, "
          f"coll {rec['collective_bytes']/2**30:.3f} GiB")
    print("memory_analysis:", rec["memory"])
    print("cost_analysis:", {k: f"{v:.3e}" for k, v in rec["cost"].items()
                             if "flops" in k or "bytes" in k})
    return rec


def run_dsc_cell(name: str, multi_pod: bool, sim_strategy: str = "psum",
                 sim_dtype: str = "f32", suffix: str = "") -> dict:
    """Dry-run the paper's own pipeline on the production mesh."""
    from repro.core.distributed import run_dsc_distributed
    from repro.core.partitioning import PartitionedBatch
    from repro.core.plan import EnginePlan
    from repro.core.types import DSCParams

    rc = get_dsc_config(name)
    mesh = make_dsc_mesh(multi_pod=multi_pod)
    nP = mesh.shape["part"]
    T = max(rc.n_trajs, nP * 16)
    T = -(-T // (nP * 16)) * (nP * 16)      # divisible by both axes
    Mp = rc.max_points
    parts = PartitionedBatch(
        x=jax.ShapeDtypeStruct((nP, T, Mp), jnp.float32),
        y=jax.ShapeDtypeStruct((nP, T, Mp), jnp.float32),
        t=jax.ShapeDtypeStruct((nP, T, Mp), jnp.float32),
        valid=jax.ShapeDtypeStruct((nP, T, Mp), jnp.bool_),
        traj_id=jax.ShapeDtypeStruct((T,), jnp.int32),
        ranges=jax.ShapeDtypeStruct((nP, 2), jnp.float32),
    )
    params = DSCParams(
        eps_sp=rc.eps_sp, eps_t=rc.eps_t, delta_t=rc.delta_t, w=rc.w,
        tau=rc.tau, alpha_sigma=rc.alpha_sigma, k_sigma=rc.k_sigma,
        max_subtrajs_per_traj=rc.max_subtrajs, segmentation=rc.segmentation)

    t0 = time.time()
    from repro.core import distributed as dsc_dist
    import functools

    lowered = jax.jit(
        functools.partial(dsc_dist.run_dsc_distributed_lowerable,
                          params=params, mesh=mesh,
                          plan=EnginePlan(sim_strategy=sim_strategy,
                                          sim_dtype=sim_dtype))).lower(parts)
    compiled = lowered.compile()
    t1 = time.time()
    text = compiled.as_text()
    corrected = hlo_analysis.analyze_hlo(text)
    rec = {
        "arch": name, "shape": f"T{T}xMp{Mp}",
        "mesh": "multi" if multi_pod else "single",
        "status": "OK", "compile_seconds": round(t1 - t0, 1),
        "memory": _mem_dict(compiled.memory_analysis()),
        "cost": _cost_dict(compiled.cost_analysis()),
        "corrected_flops": corrected["flops"],
        "hbm_traffic_bytes": corrected["hbm_traffic_bytes"],
        "hbm_traffic_fused_bytes": corrected["hbm_traffic_fused_bytes"],
        "collective_bytes": corrected["collective_bytes"],
        "collectives": corrected["collectives"],
        "devices": int(np.prod(list(mesh.shape.values()))),
        "mesh_shape": dict(mesh.shape),
    }
    rec["sim_strategy"] = sim_strategy
    rec["sim_dtype"] = sim_dtype
    import gzip
    with gzip.open(RESULTS / f"{name}_{rec['mesh']}{suffix}.hlo.txt.gz",
                   "wt") as fh:
        fh.write(text)
    print(f"[dryrun] DSC {name} x {rec['mesh']}: compile "
          f"{rec['compile_seconds']}s")
    print("memory_analysis:", rec["memory"])
    print("cost_analysis:", {k: f"{v:.3e}" for k, v in rec["cost"].items()})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--dsc", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--policy", default="tp",
                    choices=["tp", "dp_only", "dp_fsdp"])
    ap.add_argument("--moe-quant", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--moe-cap", type=float, default=None)
    ap.add_argument("--remat-dots", action="store_true")
    ap.add_argument("--sim-strategy", default="psum",
                    choices=["psum", "allgather"])
    ap.add_argument("--sim-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--suffix", default="",
                    help="output-name suffix for hillclimb variants")
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    if args.dsc:
        for mp in meshes:
            cells.append(("dsc", args.dsc, mp))
    elif args.all:
        for arch in ARCHITECTURES:
            for shape in SHAPES:
                for mp in meshes:
                    cells.append(("lm", (arch, shape), mp))
        for name in DSC_CONFIGS:
            for mp in meshes:
                cells.append(("dsc", name, mp))
    else:
        for mp in meshes:
            cells.append(("lm", (args.arch, args.shape), mp))

    failures = 0
    for kind, what, mp in cells:
        key = (f"{what[0]}_{what[1]}" if kind == "lm" else what) + \
            ("_multi" if mp else "_single") + args.suffix
        out_path = RESULTS / f"{key}.json"
        if out_path.exists():
            log.info("skip cached %s", key)
            continue
        try:
            if kind == "lm":
                rec = run_cell(what[0], what[1], mp,
                               save_hlo=args.save_hlo,
                               policy=args.policy,
                               moe_quant=args.moe_quant,
                               moe_cap=args.moe_cap,
                               remat=("dots" if args.remat_dots
                                      else not args.no_remat),
                               suffix=args.suffix)
            else:
                rec = run_dsc_cell(what, mp,
                                   sim_strategy=args.sim_strategy,
                                   sim_dtype=args.sim_dtype,
                                   suffix=args.suffix)
        except Exception as e:      # noqa: BLE001 — record and continue
            rec = {"arch": str(what), "mesh": "multi" if mp else "single",
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            failures += 1
            log.error("FAIL %s: %s", key, e)
        out_path.write_text(json.dumps(rec, indent=1, default=str))
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
