"""Paper Fig. 8: scalability — (a) dataset-size sweep with per-phase
breakdown (Join / RSE / Clustering / RefineResults), (b) node-count sweep
(partition parallelism via subprocess with forced host devices)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np

from benchmarks.common import csv_row
from repro.core import geometry, segmentation, similarity, voting
from repro.core.clustering import cluster
from repro.core.types import DSCParams
from repro.data.synthetic import ais_like, default_dsc_params_for

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _phase_times(batch, params):
    """Time the pipeline phases separately (jitted, median of 2)."""
    import jax.numpy as jnp

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0, out

    join_fn = jax.jit(lambda b: geometry.subtrajectory_join(
        b, b, params.eps_sp, params.eps_t, params.delta_t))
    t_join, join = timed(join_fn, batch)

    def rse(b, j):
        vote = voting.point_voting(j)
        nv = voting.normalized_voting(vote, b.valid)
        seg = segmentation.tsa1(nv, b.valid, params.w, params.tau,
                                params.max_subtrajs_per_traj)
        table = similarity.build_subtraj_table(
            b, seg, vote, params.max_subtrajs_per_traj)
        return seg, table, vote

    rse_fn = jax.jit(rse)
    t_rse, (seg, table, vote) = timed(rse_fn, batch, join)

    sim_fn = jax.jit(lambda j, s, t: similarity.similarity_matrix(
        j, s, s.sub_local, t, params.max_subtrajs_per_traj))
    t_sim, sim = timed(sim_fn, join, seg, table)

    clu_fn = jax.jit(lambda s, t: cluster(s, t, params))
    t_clu, _ = timed(clu_fn, sim, table)
    return {"join": t_join, "rse": t_rse + t_sim, "cluster": t_clu}


def run():
    # (a) dataset size sweep
    for frac in (0.2, 0.4, 0.6, 0.8, 1.0):
        n = int(48 * frac)
        batch, _ = ais_like(n_vessels=n, max_points=64, seed=5)
        diam, mean_dt = default_dsc_params_for(batch)
        params = DSCParams(eps_sp=0.08 * diam, eps_t=2 * mean_dt,
                           delta_t=0.0, w=6, tau=0.2, alpha_sigma=-1.0,
                           k_sigma=-1.0)
        ph = _phase_times(batch, params)
        total = sum(ph.values())
        csv_row(f"fig8a_size_{int(frac*100)}pct", total * 1e6,
                f"join={ph['join']*1e3:.1f}ms;rse={ph['rse']*1e3:.1f}ms;"
                f"cluster={ph['cluster']*1e3:.1f}ms")

    # (b) node sweep: same data, more partitions (subprocess per point)
    driver = textwrap.dedent("""
        import os, json, time, sys
        P = int(sys.argv[1])
        # forced host devices: stay off any chip the parent holds
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=%d" % max(2*P, 2))
        import jax
        from repro.core.distributed import run_dsc_distributed
        from repro.core.partitioning import partition_batch
        from repro.core.types import DSCParams
        from repro.data.synthetic import ais_like, default_dsc_params_for
        batch, _ = ais_like(n_vessels=32, max_points=64, seed=5)
        diam, mean_dt = default_dsc_params_for(batch)
        params = DSCParams(eps_sp=0.08*diam, eps_t=2*mean_dt, w=6, tau=0.2,
                           alpha_sigma=-1.0, k_sigma=-1.0)
        mesh = jax.make_mesh((P, 2), ("part", "model"))
        parts = partition_batch(batch, P)
        out = run_dsc_distributed(parts, params, mesh)   # compile
        jax.block_until_ready(out.result.member_of)
        t0 = time.perf_counter()
        out = run_dsc_distributed(parts, params, mesh)
        jax.block_until_ready(out.result.member_of)
        print("TIME", time.perf_counter() - t0)
    """)
    for P in (1, 2, 4):
        env = dict(os.environ, PYTHONPATH=SRC)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run([sys.executable, "-c", driver, str(P)],
                              env=env, capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            csv_row(f"fig8b_nodes_{P}", -1, "FAIL")
            continue
        t = float([l for l in proc.stdout.splitlines()
                   if l.startswith("TIME")][-1].split()[1])
        csv_row(f"fig8b_nodes_{P}", t * 1e6,
                f"partitions={P};model_par=2")


if __name__ == "__main__":
    run()
