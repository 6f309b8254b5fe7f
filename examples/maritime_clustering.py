"""End-to-end driver: distributed DSC over AIS-like maritime traffic.

Generates Brest-style lane traffic (variable sampling rate, temporal
displacement), temporally partitions it (equi-depth), and runs the
*distributed* pipeline on a ('part', 'model') mesh of forced host devices —
the same program the dry-run lowers for the production pod.

    PYTHONPATH=src python examples/maritime_clustering.py
"""
import os
# the mesh is 8 forced host devices, on the CPU even where a chip exists
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np
import jax

from repro.core.distributed import run_dsc_distributed
from repro.core.partitioning import partition_batch
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams
from repro.data.synthetic import ais_like, default_dsc_params_for


def main():
    batch, lanes = ais_like(n_vessels=48, n_lanes=4, max_points=96,
                            seed=7)
    diam, mean_dt = default_dsc_params_for(batch)
    params = DSCParams(eps_sp=0.08 * diam, eps_t=2.0 * mean_dt,
                       delta_t=4.0 * mean_dt, w=6, tau=0.2,
                       alpha_sigma=-1.0, k_sigma=-1.0,
                       segmentation="tsa1")

    mesh = jax.make_mesh((4, 2), ("part", "model"))
    parts = partition_batch(batch, 4)
    out = run_dsc_distributed(parts, params, mesh,
                              plan=EnginePlan(use_kernel=True))

    res = out.result
    member_of = np.asarray(res.member_of)
    is_rep = np.asarray(res.is_rep)
    reps = np.nonzero(is_rep)[0]
    maxs = params.max_subtrajs_per_traj
    print(f"vessels: {batch.num_trajs}, lanes: 4, partitions: 4, "
          f"model-parallel: 2")
    print(f"clusters: {len(reps)}, outliers: "
          f"{int(np.asarray(res.is_outlier).sum())}")
    for rep in reps[:10]:
        members = np.nonzero(member_of == rep)[0]
        vessels = sorted({int(m) // maxs for m in members})
        lane_ids = sorted({int(lanes[vv]) for vv in vessels})
        print(f"  cluster rep={int(rep)} size={len(members)} "
              f"lanes={lane_ids}")


if __name__ == "__main__":
    main()
