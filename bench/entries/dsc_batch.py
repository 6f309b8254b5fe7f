"""System under test for a single-host batch cell: ``repro.core.dsc.run_dsc``.

One call is one batch job: the host arrays go to the device, ``run_dsc``
plans its index tiles on the host and dispatches its stage programs, and
the call ends when the final labels are ready on the device.
"""
from __future__ import annotations

import contextlib

import jax
from jax.profiler import TraceAnnotation


class Runner:
    def __init__(self, cfg: dict, devices):
        from repro.core.plan import EnginePlan
        self.cfg = cfg
        self.plan = EnginePlan(**cfg["plan"])
        self.device = devices[0]

    def params(self, batch):
        from repro.core.types import DSCParams
        c = self.cfg
        return DSCParams(
            eps_sp=batch.eps_sp, eps_t=batch.eps_t, delta_t=c["delta_t"],
            w=c["w"], tau=c["tau"], alpha_sigma=c["alpha_sigma"],
            k_sigma=c["k_sigma"], max_subtrajs_per_traj=c["max_subtrajs"],
            segmentation=c["segmentation"])

    def to_device(self, batch):
        from repro.core.types import TrajectoryBatch
        put = lambda a: jax.device_put(a, self.device)
        return TrajectoryBatch(x=put(batch.x), y=put(batch.y), t=put(batch.t),
                               valid=put(batch.valid),
                               traj_id=put(batch.traj_id))

    @contextlib.contextmanager
    def watch(self, counters: dict):
        """While open, keep every tile plan of ``run_dsc``'s two index
        plannings: the similarity sweep's (``plan_fused_tiles``) under
        ``counters["sweep_tiles"]`` and the join's (``plan_join_index``)
        under ``counters["join_tiles"]``, each as ``(plan, total)``: the
        sweep's ``[row blocks, K]`` tile ids (-1 padded) or the join's
        ``[row blocks, candidate blocks]`` mask, and the count of all
        tiles.  Nothing is computed from them inside the window."""
        from repro.kernels.stjoin import ops
        sweep = counters.setdefault("sweep_tiles", [])
        join = counters.setdefault("join_tiles", [])
        real_sweep, real_join = ops.plan_fused_tiles, ops.plan_join_index

        def plan_fused_tiles(*a, **k):
            tp = real_sweep(*a, **k)
            n_cand = -(-a[4].shape[0] // tp.bc)
            sweep.append((tp.tile_ids, tp.tile_ids.shape[0] * n_cand))
            return tp

        def plan_join_index(*a, **k):
            mask, counts, spec = real_join(*a, **k)
            join.append((mask, mask.size))
            return mask, counts, spec

        ops.plan_fused_tiles, ops.plan_join_index = (plan_fused_tiles,
                                                     plan_join_index)
        try:
            yield counters
        finally:
            ops.plan_fused_tiles, ops.plan_join_index = real_sweep, real_join

    def run(self, batch, params):
        """Labels ``(member_of, member_sim, is_rep, is_outlier)`` on the
        device, ready."""
        from repro.core.dsc import run_dsc
        with TraceAnnotation("bench.put"):
            tb = self.to_device(batch)
        with TraceAnnotation("bench.entry"):
            out = run_dsc(tb, params, plan=self.plan)
            r = out.result
            labels = (r.member_of, r.member_sim, r.is_rep, r.is_outlier)
            del out, r
        with TraceAnnotation("bench.wait"):
            return jax.block_until_ready(labels)

