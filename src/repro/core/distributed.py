"""Distributed Subtrajectory Clustering — the paper's two MapReduce jobs as a
single jit-compiled ``shard_map`` program (Problem 4).

Mesh axes
---------
``part``  : temporal partitions (the paper's equi-depth bins).  On the
            production mesh this is the folded (pod, data) axes.
``model`` : candidate-trajectory parallelism — the best-match tensor
            ``B[point, cand_traj]`` is column-sharded; votes / similarity
            matrices are psum-reduced.  This is the scale-out lever the
            paper's per-trajectory reduce task lacks.

Phase structure (all inside ONE shard_map body — no host round-trips):

  1. JOIN        ppermute halo exchange of neighbor partition slabs,
                 best-match join (Pallas kernel or jnp ref), delta_t refine,
                 vote psum over 'model'.
  2. REGROUP     all_to_all over 'part': row-aligned partition slabs
                 [T, Mp] -> per-home-shard full trajectories [T/P, P*Mp];
                 compaction (valid-prefix) for windowed segmentation.
  3. SEGMENT     TSA1 / TSA2 on full trajectories (exactly the paper's Job 1
                 reduce); ST relation; labels scattered back via the inverse
                 all_to_all + ppermute of the label halo.
  4. SIMILARITY  per-partition scatter-add of join weights into the dense
                 SP matrix, psum over 'model'; Eq. 2 normalization.
  5. CLUSTER     Algorithm 4 per partition (thresholds resolved per
                 partition, Sec. 6.1).
  6. REFINE      all_gather over 'part' + the Algorithm 5 case-table
                 reduction -> one consistent global result, replicated.

The phases are methods on ``_DSCProgramBuilder`` so two compositions share
them verbatim: :func:`build_dsc_program` (the monolithic program above) and
:func:`build_dsc_stage_programs` (one program per checkpointable stage
boundary, the distributed half of the resilient runner
``repro.run.resilient`` — DESIGN.md §10).  Stage-k output fed to stage k+1
re-enters exactly the code the monolith would have run next, which is the
resume bit-identity argument.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import segmentation as seg_mod
from repro.core.clustering import cluster
from repro.core.geometry import refine_join
from repro.core.partitioning import PartitionedBatch
from repro.core.plan import EnginePlan, resolve_plan
from repro.core.refine import refine_states
from repro.core.similarity import (build_subtraj_table_arrays, finalize_sim,
                                   finalize_sim_cols,
                                   merge_topk_blocks, merge_topk_lists,
                                   sim_row_moments, topk_overflow)
from repro.core.voting import normalized_voting
from repro.core.types import (ClusteringResult, DSCParams, JoinResult,
                              SubtrajTable, TopKSim, TrajectoryBatch)
from repro.core.windows import pack_bits
from repro.utils.tree import pytree_dataclass

# stage-state donation is best-effort: when a stage's outputs can't alias
# a donated input buffer XLA still frees it at call time (the memory win
# the resilient loop wants) — silence the per-compile nag about the
# unused alias
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@pytree_dataclass
class DistributedDSCOutput:
    result: ClusteringResult      # [S] global, replicated
    table: SubtrajTable           # [S] global, replicated
    vote: jnp.ndarray             # [P, T, Mp] partition layout
    active: jnp.ndarray           # [P, S] subtraj-in-partition masks
    sim_diag: jnp.ndarray         # [P, 4] (mean sim>0, alpha, k, topk
                                  # overflow count) per partition


def _nbr(x, axis, shift, n):
    """Slab from the partition at distance ``shift``; zeros at the edge."""
    perm = [(i, i + shift) for i in range(n) if 0 <= i + shift < n]
    return lax.ppermute(x, axis, perm)


def _ring_gather(x, axis, n):
    """Forwarding-ring ``all_gather``: ``n - 1`` ``ppermute`` hops, each
    rank passing along the block it received last step, assembled into the
    same ``[n, ...]`` stack ``lax.all_gather`` returns.

    Pure data movement, so the result is bit-identical to the barrier
    gather — but the per-step wire payload is a constant ``1/n`` of the
    barrier payload, and because each landed block is a separate value in
    the dataflow graph the consumer's compute on block ``s`` can overlap
    the transfer of block ``s + 1`` (DESIGN.md §12)."""
    if n == 1:
        return x[None]
    r = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, r, 0)
    buf = x
    for s in range(1, n):
        buf = lax.ppermute(buf, axis, perm)
        # after s forwarding hops the buffer holds rank (r - s)'s block
        out = lax.dynamic_update_index_in_dim(out, buf, (r - s) % n, 0)
    return out


def run_dsc_distributed(
    parts: PartitionedBatch,
    params: DSCParams,
    mesh: Mesh,
    *,
    part_axis: str = "part",
    model_axis: str = "model",
    on_overflow: str = "raise",
    plan: EnginePlan | None = None,
    **kw,
) -> DistributedDSCOutput:
    """Compile & run the full distributed pipeline on ``mesh``.

    ``plan=`` takes one :class:`EnginePlan`; the remaining keyword
    arguments are the deprecated per-stage aliases (``use_kernel``,
    ``use_index``, ``mode``, ``sim_mode``, ... — see
    ``build_dsc_program``) that materialize a plan when none is given.

    Under ``sim_mode="topk"`` the per-partition exactness certificate is
    checked on the host and ``on_overflow`` names the policy
    (DESIGN.md §10): ``"raise"`` (default, the historical behavior)
    fails loudly; ``"widen"`` rebuilds the program with K doubled and
    reruns until the certificate holds (the fully-jitted program cannot
    widen in-graph the way ``run_dsc`` retries — the resilient runner's
    stage-level widen restarts from the checkpointed join state
    instead); ``"degrade"`` returns the truncated result, with the
    violation count recorded in ``sim_diag[:, 3]``.
    """
    if on_overflow not in ("raise", "widen", "degrade"):
        raise ValueError(f"on_overflow={on_overflow!r}: expected "
                         "'raise', 'widen', or 'degrade'")
    plan = resolve_plan(plan, **kw)
    S = parts.x.shape[1] * params.max_subtrajs_per_traj
    while True:
        fn = build_dsc_program(parts, params, mesh, part_axis=part_axis,
                               model_axis=model_axis, plan=plan)
        final, table, vote, active, diag = jax.jit(fn)(
            parts.x, parts.y, parts.t, parts.valid, parts.traj_id,
            parts.ranges)
        out = DistributedDSCOutput(
            result=final, table=table, vote=vote, active=active,
            sim_diag=diag)
        if plan.sim_mode != "topk":
            return out
        import numpy as np
        overflow = int(np.asarray(diag)[:, 3].sum())
        if overflow == 0 or on_overflow == "degrade":
            return out
        k = plan.sim_topk if plan.sim_topk is not None else 32
        if on_overflow == "raise":
            raise RuntimeError(
                f"sim_topk={k} truncated potential "
                f"alpha-edges on {overflow} rows across partitions "
                "(spill >= alpha): labels would not be exact.  Rerun "
                "with a larger sim_topk.")
        if k >= S:              # unreachable: K == S cannot spill
            raise AssertionError("overflow with K == S")
        plan = plan.replace(sim_topk=min(2 * k, S))


def run_dsc_distributed_lowerable(parts: PartitionedBatch,
                                  params: DSCParams, mesh: Mesh,
                                  **kw):
    """jit-friendly entry (parts as a pytree arg) for the dry-run."""
    fn = build_dsc_program(parts, params, mesh, **kw)
    return fn(parts.x, parts.y, parts.t, parts.valid, parts.traj_id,
              parts.ranges)


class _DSCProgramBuilder:
    """Mesh geometry + the six phase bodies, shared verbatim by the
    monolithic program and the per-stage programs."""

    def __init__(self, parts: PartitionedBatch, params: DSCParams,
                 mesh: Mesh, part_axis: str, model_axis: str,
                 plan: EnginePlan):
        self.params = params
        self.mesh = mesh
        self.part_axis = part_axis
        self.model_axis = model_axis
        self.plan = plan
        self.mode = plan.mode
        self.use_kernel = plan.use_kernel
        self.use_index = plan.use_index
        self.sim_strategy = plan.sim_strategy
        self.sim_dtype = plan.sim_dtype
        self.halo_stream = plan.halo_stream
        self.sim_exchange = plan.sim_exchange
        self.cluster_engine = plan.cluster_engine
        self.cluster_use_kernel = plan.cluster_use_kernel
        self.seg_use_kernel = plan.seg_use_kernel
        self.sim_mode = plan.sim_mode
        self.sim_topk = plan.sim_topk if plan.sim_topk is not None else 32
        # fused tile-geometry overrides for the streaming sweeps (None =
        # the kernels' own defaults — identical traces to the pre-plan
        # surface)
        self.tile_kw = ({} if plan.fused_tiles is None else
                        dict(zip(("rows", "bc", "bm"), plan.fused_tiles)))
        self.nP = mesh.shape[part_axis]
        self.nM = mesh.shape[model_axis]
        Pn, T, Mp = parts.x.shape
        assert Pn == self.nP, f"partitions {Pn} != mesh axis {self.nP}"
        assert T % self.nP == 0, f"T={T} must divide partitions {self.nP}"
        assert T % self.nM == 0, f"T={T} must divide model axis {self.nM}"
        self.T, self.Mp = T, Mp
        self.maxS = params.max_subtrajs_per_traj
        self.S = T * self.maxS
        self.Tl = T // self.nP       # home trajectories per shard
        self.Tc = T // self.nM       # candidate columns per model rank
        self.Mtot = self.nP * Mp     # full per-trajectory point capacity

    # ------------------------------------------------------------ helpers
    def halo(self, arr):
        l = _nbr(arr, self.part_axis, +1, self.nP)
        r = _nbr(arr, self.part_axis, -1, self.nP)
        return l, r

    def _gather_model(self, x, schedule):
        """Model-axis gather under the named comm schedule — ``"barrier"``
        / ``"allgather"`` is one ``lax.all_gather``, ``"ring"`` the
        forwarding-ring twin (bit-identical stack, 1/nM per-step
        payload)."""
        if schedule == "ring":
            return _ring_gather(x, self.model_axis, self.nM)
        return lax.all_gather(x, self.model_axis)

    def _cand_slice(self):
        """(c0, slicer, per-rank traj-id slicer) for this model rank."""
        mrank = lax.axis_index(self.model_axis)
        c0 = mrank * self.Tc
        sl = lambda a: lax.dynamic_slice_in_dim(a, c0, self.Tc, axis=0)
        return c0, sl

    def halo_points(self, px, py, pt, pv, rng):
        """Phase 1 front half: neighbor slab exchange (+ index pruning
        and the partition time-range mask) -> [T, 3Mp] concatenations."""
        params, nP = self.params, self.nP
        lx, rx = self.halo(px)
        ly, ry = self.halo(py)
        lt, rt = self.halo(pt)
        if self.use_index:
            # index-pruned halo: exchange eps-expanded partition bboxes
            # (6 floats) first, then ship each neighbor only the bucket of
            # points it can actually match (conservative -> same result).
            inf = jnp.float32(jnp.inf)
            own_box = jnp.stack([
                jnp.min(jnp.where(pv, px, inf)),
                jnp.max(jnp.where(pv, px, -inf)),
                jnp.min(jnp.where(pv, py, inf)),
                jnp.max(jnp.where(pv, py, -inf)),
                jnp.min(jnp.where(pv, pt, inf)),
                jnp.max(jnp.where(pv, pt, -inf)),
            ])
            box_l = _nbr(own_box, self.part_axis, +1, nP)  # bbox of rank-1
            box_r = _nbr(own_box, self.part_axis, -1, nP)  # bbox of rank+1
            e_sp = jnp.asarray(params.eps_sp, jnp.float32)
            e_t = jnp.asarray(params.eps_t, jnp.float32)

            def inside(box):
                return ((px >= box[0] - e_sp) & (px <= box[1] + e_sp)
                        & (py >= box[2] - e_sp) & (py <= box[3] + e_sp)
                        & (pt >= box[4] - e_t) & (pt <= box[5] + e_t))

            lv = _nbr(pv & inside(box_r), self.part_axis, +1, nP)
            rv = _nbr(pv & inside(box_l), self.part_axis, -1, nP)
        else:
            lv, rv = self.halo(pv)
        eps_t = jnp.asarray(params.eps_t, jnp.float32)
        lo, hi = rng[0] - eps_t, rng[1] + eps_t
        lv &= (lt >= lo) & (lt <= hi)
        rv &= (rt >= lo) & (rt <= hi)

        cx = jnp.concatenate([px, lx, rx], axis=1)        # [T, 3Mp]
        cy = jnp.concatenate([py, ly, ry], axis=1)
        ct = jnp.concatenate([pt, lt, rt], axis=1)
        cv = jnp.concatenate([pv, lv, rv], axis=1)
        # per-slab views, in concat order (own, left, right): the ring
        # join schedule consumes these directly so the own-slab sweep has
        # no dataflow edge to the neighbor ppermutes — compute on slab s
        # overlaps the transfer of slab s+1
        slabs = ((px, py, pt, pv), (lx, ly, lt, lv), (rx, ry, rt, rv))
        return cx, cy, ct, cv, slabs

    # ---------------- phase 1: halo exchange + join ----------------
    def _join_slab(self, px, py, pt, pv, traj_id, cid, kx, ky, kt, kv):
        """One best-match sweep of the candidate point arrays ``[Tc, Mc]``
        — the full ``3Mp`` concat under the barrier schedule, one ``Mp``
        slab at a time under the ring schedule."""
        params, T, Mp, Tc = self.params, self.T, self.Mp, self.Tc
        if self.use_kernel:
            # the padded single-chip wrapper: lane tiles of 128 (or the
            # whole candidate axis), whatever Tc and Mc are
            from repro.kernels.stjoin.ops import best_match_join_kernel
            ref = TrajectoryBatch(x=px, y=py, t=pt, valid=pv,
                                  traj_id=traj_id)
            cand = TrajectoryBatch(x=kx, y=ky, t=kt, valid=kv, traj_id=cid)
            j, _ = best_match_join_kernel(ref, cand, params.eps_sp,
                                          params.eps_t)
            return (j.best_w.reshape(T * Mp, Tc),
                    j.best_idx.reshape(T * Mp, Tc))
        from repro.kernels.stjoin.ref import stjoin_ref
        pair_mask = None
        if self.use_index:
            from repro.index.grid import trajectory_pair_mask
            pmask = trajectory_pair_mask(
                px, py, pt, pv, kx, ky, kt, kv,
                params.eps_sp, params.eps_t)               # [T, Tc]
            pair_mask = jnp.repeat(pmask, Mp, axis=0)      # [T*Mp, Tc]
        ref_ids = jnp.broadcast_to(traj_id[:, None], (T, Mp)).reshape(-1)
        return stjoin_ref(
            px.reshape(-1), py.reshape(-1), pt.reshape(-1),
            ref_ids, pv.reshape(-1),
            kx, ky, kt, cid, kv,
            jnp.asarray(params.eps_sp, jnp.float32),
            jnp.asarray(params.eps_t, jnp.float32),
            pair_mask=pair_mask)

    def phase_join(self, px, py, pt, pv, traj_id, cx, cy, ct, cv,
                   slabs=None):
        """Returns ``(join, vote, masks)``; ``join`` is this rank's
        [T, Mp, Tc] column block, or None in fused mode.  The halo slabs
        come from :meth:`halo_points` (computed once per program).

        ``plan.halo_stream="ring"`` streams the materialize join one halo
        slab at a time — the own-slab sweep runs while the neighbor slabs
        are still in flight — and the running (best_w, best_idx) fold is
        bit-identical to the concatenated sweep because the kernels'
        argmax is first-occurrence under strict ``>`` updates, which is
        invariant to how the candidate-point axis is chunked
        (DESIGN.md §12).  Fused mode cannot decompose per slab (the
        in-kernel delta_t run refine needs every candidate point of a
        trajectory at once), so there the ring schedule instead streams
        the phase's model-axis word/mask gathers.
        """
        params, T, Mp, Tc = self.params, self.T, self.Mp, self.Tc
        c0, sl = self._cand_slice()
        cid = lax.dynamic_slice_in_dim(traj_id, c0, Tc, axis=0)

        if self.mode == "fused":
            # streaming join epilogue: per-rank fused sweep over the halo
            # slab — votes and packed neighbor words, never the
            # [T, Mp, Tc] cube.  delta_t refine happens in-kernel on the
            # slab rows.
            from repro.kernels.stjoin.ops import stjoin_vote_fused_arrays
            join = None
            vote_l, words_l = stjoin_vote_fused_arrays(
                px, py, pt, pv, traj_id,
                sl(cx), sl(cy), sl(ct), sl(cv), cid,
                params.eps_sp, params.eps_t, params.delta_t,
                with_masks=params.segmentation == "tsa2", **self.tile_kw)
            vote = lax.psum(vote_l, self.model_axis)       # [T, Mp]
            if params.segmentation == "tsa2":
                allw = self._gather_model(words_l, self.halo_stream)
                masks = jnp.moveaxis(allw, 0, 2).reshape(
                    T, Mp, self.nM * words_l.shape[-1])
            else:
                masks = jnp.zeros((T, Mp, 1), jnp.uint32)
            return join, vote, masks

        if self.halo_stream == "ring" and slabs is not None:
            # slab-streamed join: fold each slab's sweep as it lands.
            # Slab order mirrors the concat (own, left, right); strict
            # ``>`` keeps the first occurrence of the running max, so the
            # fold reproduces the concatenated argmax bit for bit.
            bw = jnp.zeros((T * Mp, Tc), jnp.float32)
            bidx = jnp.full((T * Mp, Tc), -1, jnp.int32)
            for off, (sx, sy, st, sv) in zip((0, Mp, 2 * Mp), slabs):
                w_s, i_s = self._join_slab(px, py, pt, pv, traj_id, cid,
                                           sl(sx), sl(sy), sl(st), sl(sv))
                better = w_s > bw
                bidx = jnp.where(better, i_s + off, bidx)
                bw = jnp.where(better, w_s, bw)
        else:
            bw, bidx = self._join_slab(px, py, pt, pv, traj_id, cid,
                                       sl(cx), sl(cy), sl(ct), sl(cv))

        join = JoinResult(best_w=bw.reshape(T, Mp, Tc),
                          best_idx=bidx.reshape(T, Mp, Tc))
        join = refine_join(join, pt, params.delta_t)

        vote = lax.psum(
            jnp.sum(join.best_w, axis=-1), self.model_axis)  # [T, Mp]

        if params.segmentation == "tsa2":
            matched = join.best_w > 0.0                    # [T, Mp, Tc]
            allm = self._gather_model(matched, self.halo_stream)
            allm = jnp.moveaxis(allm, 0, 2).reshape(T, Mp, self.nM * Tc)
            masks = pack_bits(allm)                        # [T, Mp, W]
        else:
            masks = jnp.zeros((T, Mp, 1), jnp.uint32)
        return join, vote, masks

    # ------------- phases 2+3: regroup + segmentation (Job 1) -----------
    def phase_segment(self, pt, pv, vote, masks):
        """Returns ``(table, labels)``: the replicated global subtraj
        table and the per-partition ``sub_local`` labels [T, Mp]."""
        params, nP, Tl, Mp, Mtot = (self.params, self.nP, self.Tl,
                                    self.Mp, self.Mtot)
        maxS, T, S = self.maxS, self.T, self.S

        def regroup(a):      # [T, Mp, ...] -> [Tl, nP * Mp, ...]
            a = a.reshape(nP, Tl, *a.shape[1:])
            a = lax.all_to_all(a, self.part_axis, split_axis=0,
                               concat_axis=1)
            # [Tl, nP, Mp, ...] -> [Tl, nP*Mp, ...]
            return a.reshape(Tl, nP * Mp, *a.shape[3:])

        g_vote = regroup(vote)
        g_t = regroup(pt)
        g_v = regroup(pv)
        g_masks = regroup(masks) if params.segmentation == "tsa2" else None

        # compact: valid points first (windows need a contiguous prefix)
        key = (jnp.where(g_v, 0, 1) * (Mtot + 1)
               + jnp.arange(Mtot)[None, :])
        order = jnp.argsort(key, axis=1)
        inv_order = jnp.argsort(order, axis=1)
        takev = lambda a: jnp.take_along_axis(a, order, axis=1)
        c_vote, c_t, c_v = takev(g_vote), takev(g_t), takev(g_v)

        if params.segmentation == "tsa1":
            # Eq. 5 lives in exactly one place: the single-host voting op
            # applies per-trajectory max-normalization verbatim here
            nvote = normalized_voting(c_vote, c_v)
            seg = seg_mod.tsa1(nvote, c_v, params.w, params.tau, maxS)
        else:
            c_masks = jnp.take_along_axis(
                g_masks, order[..., None], axis=1)
            seg = seg_mod.tsa2(c_masks, c_v, params.w, params.tau, maxS,
                               use_kernel=self.seg_use_kernel)

        table_l = build_subtraj_table_arrays(
            c_t, c_v, seg.sub_local, c_vote, maxS)         # S_l = Tl*maxS

        def gather_table(x):
            g = lax.all_gather(x, self.part_axis)          # [nP, S_l]
            return g.reshape(S, *x.shape[1:])

        table = SubtrajTable(
            t_start=gather_table(table_l.t_start),
            t_end=gather_table(table_l.t_end),
            voting=gather_table(table_l.voting),
            card=gather_table(table_l.card),
            valid=gather_table(table_l.valid),
            traj_row=jnp.repeat(jnp.arange(T, dtype=jnp.int32), maxS))

        # labels back to partition layout
        sub_padded = jnp.take_along_axis(seg.sub_local, inv_order, axis=1)
        sub_padded = sub_padded.reshape(Tl, nP, Mp)
        labels = lax.all_to_all(
            sub_padded, self.part_axis, split_axis=1, concat_axis=0)
        labels = labels.reshape(T, Mp)                    # [T, Mp] sub_local
        return table, labels

    def gids(self, labels, pv, cv):
        """Global subtraj ids for own points + the label halo [T, 3Mp]."""
        T, maxS, S = self.T, self.maxS, self.S
        gid_own = jnp.where(
            (labels >= 0) & pv,
            jnp.arange(T, dtype=jnp.int32)[:, None] * maxS + labels, S)

        # candidate labels: same halo structure as the points
        ll, rl = self.halo(jnp.where(labels >= 0, labels, -1))
        lab_cat = jnp.concatenate(
            [jnp.where(labels >= 0, labels, -1), ll, rl], axis=1)
        gid_cat = jnp.where(
            (lab_cat >= 0) & cv,
            jnp.arange(T, dtype=jnp.int32)[:, None] * maxS + lab_cat, S)
        return gid_own, gid_cat

    # ---------------- phase 4: similarity (SP relation) -----------------
    def phase_similarity(self, px, py, pt, pv, traj_id, cx, cy, ct, cv,
                         join, gid_own, gid_cat, table):
        """Returns ``(sim, topk, moments, active)`` — exactly one of
        ``sim`` / ``topk`` is non-None; ``moments`` rides inside the
        TopKSim in topk mode (None here)."""
        params, T, Mp, Tc, S = self.params, self.T, self.Mp, self.Tc, self.S
        maxS = self.maxS
        c0, sl = self._cand_slice()
        cid = lax.dynamic_slice_in_dim(traj_id, c0, Tc, axis=0)
        gid_cand = sl(gid_cat)                             # [Tc, 3Mp]
        S_loc = Tc * maxS
        c0s = c0 * maxS
        # a slot id is row * maxS + sub-slot (S = unsegmented)
        sub_of = lambda gid: jnp.where(gid < S, gid % maxS, -1)
        if self.mode != "fused":
            idx = jnp.clip(join.best_idx, 0, 3 * Mp - 1)
            dst = jnp.where(
                join.best_idx >= 0,
                gid_cand[jnp.arange(Tc)[None, None, :], idx],
                S)                                         # [T, Mp, Tc]
            src = jnp.broadcast_to(gid_own[:, :, None], (T, Mp, Tc))

        # subtrajectories active in THIS partition
        active = jnp.zeros((S + 1,), bool).at[gid_own.reshape(-1)].set(
            True, mode="drop")[:S]
        part_table = table.replace(valid=table.valid & active)
        part_valid = part_table.valid

        def rank_raw_block():
            """This rank's [S, S_loc] candidate-column block of ``raw``."""
            if self.mode == "fused":
                # pass 2: re-sweep the halo slab, scatter refined weights
                # into this rank's column block in-kernel
                from repro.kernels.stjoin.ops import stjoin_sim_fused_arrays
                return stjoin_sim_fused_arrays(
                    px, py, pt, pv, traj_id, sub_of(gid_own),
                    sl(cx), sl(cy), sl(ct), sl(cv), cid, sub_of(gid_cand),
                    maxS, params.eps_sp, params.eps_t, params.delta_t,
                    **self.tile_kw)
            dst_l = jnp.where(dst < S, dst - c0s, S_loc)
            raw = jnp.zeros((S + 1, S_loc + 1), jnp.float32)
            raw = raw.at[src.reshape(-1), dst_l.reshape(-1)].add(
                join.best_w.reshape(-1))
            return raw[:S, :S_loc]

        def moments_psum(sim_block):
            """Threshold row moments from this rank's final column block,
            psum'd — both SP representations feed bit-identical inputs,
            so dense and topk resolve the exact same alpha."""
            col_valid = lax.dynamic_slice_in_dim(part_valid, c0s, S_loc)
            cnt, rsum, rsumsq = sim_row_moments(
                sim_block, part_valid, col_valid)
            return (lax.psum(cnt, self.model_axis),
                    lax.psum(rsum, self.model_axis),
                    lax.psum(rsumsq, self.model_axis))

        if self.sim_mode == "topk":
            K = min(self.sim_topk, S)
            raw_blk = rank_raw_block()                     # [S, S_loc]
            if self.sim_exchange == "ring":
                # shifted-ppermute transpose exchange: at step s every
                # rank ships the [S_loc, S_loc] sub-block destined for
                # rank (r + s) in one hop and max-folds the sub-block
                # that just landed into its own band of ``sym``.  Each
                # band is written exactly once with the same operands as
                # the barrier all_to_all, so the fold is bit-identical —
                # but every step's transfer overlaps the previous step's
                # fold (DESIGN.md §12).
                a = raw_blk.reshape(self.nM, S_loc, S_loc)
                mrank = lax.axis_index(self.model_axis)

                def fold(sym, src_rank, chunk):
                    k0 = src_rank * S_loc
                    band = lax.dynamic_slice_in_dim(raw_blk, k0, S_loc,
                                                    axis=0)
                    return lax.dynamic_update_slice_in_dim(
                        sym, jnp.maximum(band, chunk.T), k0, axis=0)

                sym_blk = fold(raw_blk, mrank,
                               lax.dynamic_index_in_dim(a, mrank, 0,
                                                        keepdims=False))
                for s in range(1, self.nM):
                    perm = [(i, (i + s) % self.nM) for i in range(self.nM)]
                    chunk = lax.dynamic_index_in_dim(
                        a, (mrank + s) % self.nM, 0, keepdims=False)
                    sym_blk = fold(sym_blk, (mrank - s) % self.nM,
                                   lax.ppermute(chunk, self.model_axis,
                                                perm))
            else:
                # transpose-partner exchange: rank r sends raw[cols_k,
                # cols_r] to rank k and assembles raw[cols_r, :] — the
                # rows that max-symmetrize its own columns.  Each matrix
                # byte crosses the interconnect exactly once.
                a = raw_blk.reshape(self.nM, S_loc, S_loc)
                a = lax.all_to_all(a, self.model_axis, split_axis=0,
                                   concat_axis=1)
                tpart = a.reshape(S_loc, S)                # raw[cols_r, :]
                sym_blk = jnp.maximum(raw_blk, tpart.T)
            simb = finalize_sim_cols(sym_blk, c0s, table, active)
            cnt, rsum, rsumsq = moments_psum(simb)
            # per-rank top-(K+1) of the exact column block ...
            kk = min(K + 1, S_loc)
            vals, idx_l = jax.lax.top_k(simb, kk)
            lids = c0s + idx_l
            if self.sim_exchange == "ring":
                # ... streamed around the forwarding ring: fold each
                # arriving rank's list into the standing top-(K+1) via
                # the canonical pairwise merge.  Exact and
                # order-invariant (``sort_topk_lists``), so the running
                # merge equals the barrier k-way merge bit for bit while
                # replacing the global [nM, S, K+1] gather with a
                # constant [S, K+1] per-step payload.
                perm = [(i, (i + 1) % self.nM) for i in range(self.nM)]
                run_i, run_v = lids, vals
                buf_i, buf_v = lids, vals
                for s in range(1, self.nM):
                    buf_v = lax.ppermute(buf_v, self.model_axis, perm)
                    buf_i = lax.ppermute(buf_i, self.model_axis, perm)
                    run_i, run_v = merge_topk_lists(
                        run_i, run_v, buf_i, buf_v,
                        min(K + 1, (s + 1) * kk))
                ids, sims, spill = merge_topk_blocks(run_i, run_v, K)
            else:
                # barrier k-way merge of the gathered [S, K+1] lists —
                # the only replicated similarity payload
                g_vals = lax.all_gather(vals, self.model_axis)
                g_ids = lax.all_gather(lids, self.model_axis)
                m_vals = jnp.moveaxis(g_vals, 0, 1).reshape(
                    S, self.nM * kk)
                m_ids = jnp.moveaxis(g_ids, 0, 1).reshape(S, self.nM * kk)
                ids, sims, spill = merge_topk_blocks(m_ids, m_vals, K)
            topk = TopKSim(ids=ids, sims=sims, spill=spill, degree=cnt,
                           row_sum=rsum, row_sumsq=rsumsq)
            return None, topk, None, active

        if self.sim_strategy == "allgather":
            raw = rank_raw_block()
            if self.sim_dtype == "bf16":
                raw = raw.astype(jnp.bfloat16)
            gathered = self._gather_model(raw, self.sim_exchange)
            raw = jnp.moveaxis(gathered, 0, 1).reshape(S, S)
            raw = raw.astype(jnp.float32)
        else:
            if self.mode == "fused":
                from repro.kernels.stjoin.ops import \
                    stjoin_sim_fused_arrays
                blk = stjoin_sim_fused_arrays(
                    px, py, pt, pv, traj_id, sub_of(gid_own),
                    sl(cx), sl(cy), sl(ct), sl(cv), cid, sub_of(gid_cand),
                    maxS, params.eps_sp, params.eps_t, params.delta_t,
                    **self.tile_kw)                        # [S, S_loc]
                raw = lax.dynamic_update_slice_in_dim(
                    jnp.zeros((S, S), jnp.float32), blk, c0s, axis=1)
            else:
                raw = jnp.zeros((S + 1, S + 1), jnp.float32)
                raw = raw.at[src.reshape(-1), dst.reshape(-1)].add(
                    join.best_w.reshape(-1))
                raw = raw[:S, :S]
            if self.sim_dtype == "bf16":
                raw = raw.astype(jnp.bfloat16)
            raw = lax.psum(raw, self.model_axis).astype(jnp.float32)

        # Eq. 2 normalization — shared with the single-host paths (the
        # table.valid mask it adds is a no-op here: weight is only ever
        # scattered into slots that own at least one valid point)
        sim = finalize_sim(raw, table)
        sim = jnp.where(active[:, None] & active[None, :], sim, 0.0)
        moments = moments_psum(
            lax.dynamic_slice_in_dim(sim, c0s, S_loc, axis=1))
        return sim, None, moments, active

    # ------------- phase 5: per-partition clustering --------------------
    def phase_cluster(self, sim, topk, moments, table, active):
        """Returns ``(res_l, diag)`` for THIS partition's shard."""
        part_table = table.replace(valid=table.valid & active)
        if topk is not None:
            res_l = cluster(topk, part_table, self.params,
                            engine=self.cluster_engine,
                            use_kernel=self.cluster_use_kernel,
                            tiles=self.plan.cluster_tiles)
            overflow = topk_overflow(topk, res_l.alpha_used)
            meansim = jnp.sum(topk.row_sum) / jnp.maximum(
                jnp.sum(topk.degree), 1)
        else:
            res_l = cluster(sim, part_table, self.params,
                            engine=self.cluster_engine,
                            use_kernel=self.cluster_use_kernel,
                            moments=moments, tiles=self.plan.cluster_tiles)
            overflow = jnp.zeros((), jnp.int32)
            pos = sim > 0
            meansim = jnp.sum(jnp.where(pos, sim, 0.0)) / jnp.maximum(
                jnp.sum(pos), 1)
        diag = jnp.stack([meansim, res_l.alpha_used, res_l.k_used,
                          overflow.astype(jnp.float32)])
        return res_l, diag


def build_dsc_program(
    parts: PartitionedBatch,
    params: DSCParams,
    mesh: Mesh,
    *,
    part_axis: str = "part",
    model_axis: str = "model",
    plan: EnginePlan | None = None,  # the one tuned surface (DESIGN.md §9)
    use_kernel: bool = False,
    use_index: bool = False,
    mode: str = "materialize",      # "materialize" | "fused"
    sim_strategy: str = "psum",     # "psum" | "allgather" (column-sharded)
    sim_dtype: str = "f32",         # "f32" | "bf16" collective payload
    cluster_engine: str = "rounds",  # "rounds" | "sequential" (oracle)
    cluster_use_kernel: bool = False,  # Pallas tile kernels for phase 5
    seg_use_kernel: bool = False,    # Pallas TSA2 Jaccard kernel, phase 3
    sim_mode: str = "dense",        # "dense" | "topk" SP representation
    sim_topk: int | None = None,    # K of the top-K neighbor lists (32)
    halo_stream: str = "barrier",   # "barrier" | "ring" join halo schedule
    sim_exchange: str = "allgather",  # "allgather" | "ring" sim schedule
):
    """Build the shard_map program (not yet jitted) for ``parts`` shapes.

    ``plan=`` carries every per-stage choice as one :class:`EnginePlan`;
    the per-stage keywords below are **deprecated aliases** that
    materialize a plan (``repro.core.plan.resolve_plan``) — passing both
    a plan and a non-default alias raises.

    ``mode="fused"`` streams the JOIN phase per halo slab: instead of
    building the per-rank ``[T, Mp, Tc]`` join cube and re-reading it for
    votes / TSA2 masks / the SP scatter, two fused Pallas sweeps accumulate
    those outputs directly (pass 2 re-sweeps after segmentation).  The
    collective payloads shrink with the buffers: votes psum as before, TSA2
    neighbor sets all_gather as packed words (32x smaller than the bool
    ``matched`` cube), and the SP accumulator follows ``sim_strategy``
    unchanged.  ``use_index`` composes with it (the halo bbox bucketing is
    join-free); the in-kernel delta_t refine matches ``filter_delta_t`` on
    the partition slab exactly.

    ``sim_strategy="allgather"`` exploits that each model rank's scatter
    targets only ITS candidate-column block of the SP matrix: instead of a
    dense [S, S] psum (2x bytes, 16x memory), each rank all_gathers its
    [S, S/m] block — the §Perf optimization for the DSC cells.
    ``sim_dtype="bf16"`` additionally halves the payload.

    ``use_index=True`` turns on the spatiotemporal candidate-pruning index
    (``repro.index.grid``) in the JOIN phase: partitions first exchange
    their eps-expanded bounding boxes (6 floats) and tighten the validity
    mask of the slab they ship to each neighbor down to the points that
    neighbor can actually match (slab *bytes* are unchanged — fixed
    shapes — but out-of-reach points never enter the join or any
    downstream reduction), and the jnp join path additionally skips
    (ref row, cand row) pairs whose bboxes are provably farther than eps
    apart.  Both filters are conservative, so results are unchanged.

    ``cluster_engine`` selects the phase-5 engine per partition:
    ``"rounds"`` (round-parallel, default) or ``"sequential"`` (the O(S)
    oracle); outputs are label-identical (DESIGN.md §6).
    ``cluster_use_kernel=True`` backs the round engine with the Pallas
    tile kernels (``repro.kernels.cluster``) inside each partition's
    shard — the accelerator path; the jnp formulation is faster on
    CPU.

    ``seg_use_kernel=True`` runs phase 3's TSA2 Jaccard signal through
    the fused Pallas segmentation kernel (``repro.kernels.jaccard``)
    inside each shard instead of the jnp packed-word engine —
    bit-identical cuts and labels (DESIGN.md §7); a no-op under
    ``tsa1``.

    ``sim_mode="topk"`` keeps the SP relation sparse end to end
    (DESIGN.md §8): each model rank builds only its ``[S, S_loc]``
    candidate-column block of the raw scatter (``S_loc = S / m``), an
    all_to_all hands every rank the transpose-partner rows of its block
    (each byte of the matrix moves once, vs. the dense ``[S, S]``
    psum's 2x-all-reduce), rank-exact max-symmetrization + Eq. 2
    normalization happen on the block, and the only replicated payload
    is the all_gather of per-rank top-(K+1) candidate lists —
    ``[S, K+1]`` ids+sims instead of ``[S, S]``.  Phase 5 clusters on
    the merged ``TopKSim`` neighbor lists; labels are bit-identical to
    ``sim_mode="dense"`` whenever the spill certificate holds (the
    per-partition overflow count rides in ``sim_diag[:, 3]``; widen
    ``sim_topk`` when nonzero — there is no in-graph retry).  Threshold
    moments psum per-rank row partials in both modes, so dense and topk
    resolve bit-identical alpha.  ``sim_strategy`` / ``sim_dtype`` only
    shape the dense collective and are ignored under topk.

    ``halo_stream="ring"`` / ``sim_exchange="ring"`` swap the phase
    barriers for P-step ``ppermute`` ring schedules (DESIGN.md §12):
    the materialize join folds one halo slab per step while the next is
    in flight, the topk similarity exchange becomes a shifted-ppermute
    transpose sweep plus a forwarding ring over the per-rank top-(K+1)
    lists with a running canonical merge, and the dense ``allgather``
    strategy assembles its column blocks around the forwarding ring.
    Every ring schedule is bit-identical to its barrier twin; per-step
    wire payloads shrink to 1/nM of the barrier gathers.  Fused mode
    keeps the concatenated halo sweep (the in-kernel delta_t refine is
    not slab-separable) and rings only its word/mask gathers; the dense
    ``psum`` strategy is an all-reduce and ignores ``sim_exchange``."""
    plan = resolve_plan(plan, use_kernel=use_kernel, use_index=use_index,
                        mode=mode, sim_strategy=sim_strategy,
                        sim_dtype=sim_dtype, cluster_engine=cluster_engine,
                        cluster_use_kernel=cluster_use_kernel,
                        seg_use_kernel=seg_use_kernel, sim_mode=sim_mode,
                        sim_topk=sim_topk, halo_stream=halo_stream,
                        sim_exchange=sim_exchange)
    b = _DSCProgramBuilder(parts, params, mesh, part_axis, model_axis, plan)

    def body(px, py, pt, pv, traj_id, ranges):
        px, py, pt, pv = px[0], py[0], pt[0], pv[0]       # [T, Mp]
        rng = ranges[0]                                   # [2]

        # phases 1-3
        cx, cy, ct, cv, slabs = b.halo_points(px, py, pt, pv, rng)
        join, vote, masks = b.phase_join(px, py, pt, pv, traj_id,
                                         cx, cy, ct, cv, slabs)
        table, labels = b.phase_segment(pt, pv, vote, masks)
        gid_own, gid_cat = b.gids(labels, pv, cv)

        # phases 4-5
        sim, topk, moments, active = b.phase_similarity(
            px, py, pt, pv, traj_id, cx, cy, ct, cv,
            join, gid_own, gid_cat, table)
        res_l, diag = b.phase_cluster(sim, topk, moments, table, active)
        alpha, k = res_l.alpha_used, res_l.k_used

        # ---------------- phase 6: cross-partition refinement -----------
        # one packed-payload exchange instead of four separate gathers:
        # member ids ride as bitcast f32 lanes (pure data movement —
        # exact), booleans as 0.0/1.0, so the whole refinement state
        # crosses the interconnect in a single [4, S] collective
        packed = jnp.stack([
            lax.bitcast_convert_type(res_l.member_of, jnp.float32),
            res_l.member_sim,
            res_l.is_rep.astype(jnp.float32),
            active.astype(jnp.float32),
        ])                                                       # [4, S]
        g = lax.all_gather(packed, part_axis)                    # [nP, 4, S]
        final = refine_states(
            lax.bitcast_convert_type(g[:, 0], jnp.int32),
            g[:, 1], g[:, 2] > 0.5, g[:, 3] > 0.5,
            lax.pmean(alpha, part_axis), lax.pmean(k, part_axis))

        return final, table, vote[None], active[None], diag[None]

    part_spec = P(part_axis, None, None)
    in_specs = (part_spec, part_spec, part_spec, part_spec,
                P(), P(part_axis, None))
    out_specs = (P(), P(), P(part_axis, None, None),
                 P(part_axis, None), P(part_axis, None))

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)


def refine_stage(member_of, member_sim, is_rep, active, alpha, k):
    """Stage 5 of the staged distributed pipeline: the Algorithm 5
    case-table reduction on host-stacked per-partition states.  ``alpha``
    / ``k`` are the per-partition [P] vectors; their mean reproduces the
    monolith's ``lax.pmean``."""
    return refine_states(member_of, member_sim, is_rep, active,
                         jnp.mean(alpha), jnp.mean(k))


def build_dsc_stage_programs(
    parts: PartitionedBatch,
    params: DSCParams,
    mesh: Mesh,
    *,
    part_axis: str = "part",
    model_axis: str = "model",
    plan: EnginePlan | None = None,
    **kw,
) -> dict:
    """One jitted program per checkpointable stage boundary.

    Each program wraps the SAME phase bodies the monolithic
    :func:`build_dsc_program` composes, inside its own ``shard_map``, so
    running them in sequence replays the monolith's computation with a
    host round-trip (and a checkpoint) between stages.  All inter-stage
    state is exchanged as host-visible arrays:

    ``join``        ``(px..ranges) -> (vote, masks[, best_w, best_idx])``
                    The join cube is model-all_gathered to its full
                    ``[P, T, Mp, T]`` column span in materialize mode so
                    the similarity stage can re-slice each rank's block;
                    fused mode re-sweeps the halo slab instead and ships
                    no cube.
    ``segment``     ``(pt, pv, vote, masks) -> (table..., labels)``
                    (table replicated, labels ``[P, T, Mp]``).
    ``similarity``  points + labels + table (+ cube) ->
                    per-partition TopKSim fields / dense sim + moments,
                    plus the ``active`` masks.
    ``cluster``     sim state + table + active -> per-partition
                    ClusteringResult fields + ``diag [P, 4]``.
    ``refine``      :func:`refine_stage` — a plain jit over the stacked
                    per-partition states; needs no mesh.
    """
    plan = resolve_plan(plan, **kw)
    b = _DSCProgramBuilder(parts, params, mesh, part_axis, model_axis, plan)
    part2 = P(part_axis, None, None)
    part3 = P(part_axis, None, None, None)
    pts_specs = (part2, part2, part2, part2, P(), P(part_axis, None))

    def join_body(px, py, pt, pv, traj_id, ranges):
        px, py, pt, pv = px[0], py[0], pt[0], pv[0]
        cx, cy, ct, cv, slabs = b.halo_points(px, py, pt, pv, ranges[0])
        join, vote, masks = b.phase_join(px, py, pt, pv, traj_id,
                                         cx, cy, ct, cv, slabs)
        if join is None:
            return vote[None], masks[None]
        # gather the model-sharded column blocks to the full [T, Mp, T]
        # cube so the similarity stage can hand each rank its slice back
        # (ring-streamed under plan.halo_stream="ring", same bits)
        gw = b._gather_model(join.best_w, plan.halo_stream)
        gi = b._gather_model(join.best_idx, plan.halo_stream)
        bw = jnp.moveaxis(gw, 0, 2).reshape(b.T, b.Mp, b.T)
        bidx = jnp.moveaxis(gi, 0, 2).reshape(b.T, b.Mp, b.T)
        return vote[None], masks[None], bw[None], bidx[None]

    join_out = ((part2, part3) if plan.mode == "fused" else
                (part2, part3, part3, part3))
    join_fn = jax.jit(jax.shard_map(
        join_body, mesh=mesh, in_specs=pts_specs, out_specs=join_out,
        check_vma=False))

    def segment_body(pt, pv, vote, masks):
        table, labels = b.phase_segment(pt[0], pv[0], vote[0], masks[0])
        return table, labels[None]

    # the TSA2 mask cube is dead after segmentation — donating it keeps
    # checkpoint-restored state single-resident (the resilient loop holds
    # host copies, so donation never aliases a checkpoint reference)
    segment_fn = jax.jit(jax.shard_map(
        segment_body, mesh=mesh,
        in_specs=(part2, part2, part2, part3),
        out_specs=(P(), part2), check_vma=False), donate_argnums=(3,))

    def similarity_body(px, py, pt, pv, traj_id, ranges, labels, table,
                        *cube):
        px, py, pt, pv = px[0], py[0], pt[0], pv[0]
        cx, cy, ct, cv, _ = b.halo_points(px, py, pt, pv, ranges[0])
        if cube:
            c0, _ = b._cand_slice()
            join = JoinResult(
                best_w=lax.dynamic_slice_in_dim(cube[0][0], c0, b.Tc,
                                                axis=2),
                best_idx=lax.dynamic_slice_in_dim(cube[1][0], c0, b.Tc,
                                                  axis=2))
        else:
            join = None
        gid_own, gid_cat = b.gids(labels[0], pv, cv)
        sim, topk, moments, active = b.phase_similarity(
            px, py, pt, pv, traj_id, cx, cy, ct, cv,
            join, gid_own, gid_cat, table)
        if topk is not None:
            return (topk.ids[None], topk.sims[None], topk.spill[None],
                    topk.degree[None], topk.row_sum[None],
                    topk.row_sumsq[None], active[None])
        cnt, rsum, rsumsq = moments
        return (sim[None], cnt[None], rsum[None], rsumsq[None],
                active[None])

    sim_in = pts_specs + (part2, P())
    if plan.mode != "fused":
        sim_in = sim_in + (part3, part3)
    part1 = P(part_axis, None)
    sim_out = ((part2, part2, part1, part1, part1, part1, part1)
               if plan.sim_mode == "topk" else
               (part2, part1, part1, part1, part1))
    # the join cube (the largest inter-stage buffer) is dead once the
    # similarity stage has re-sliced it — donate both halves
    sim_donate = () if plan.mode == "fused" else (8, 9)
    similarity_fn = jax.jit(jax.shard_map(
        similarity_body, mesh=mesh, in_specs=sim_in, out_specs=sim_out,
        check_vma=False),
        donate_argnums=sim_donate)

    def cluster_body(table, active, *state):
        if plan.sim_mode == "topk":
            topk = TopKSim(ids=state[0][0], sims=state[1][0],
                           spill=state[2][0], degree=state[3][0],
                           row_sum=state[4][0], row_sumsq=state[5][0])
            res_l, diag = b.phase_cluster(None, topk, None, table,
                                          active[0])
        else:
            moments = (state[1][0], state[2][0], state[3][0])
            res_l, diag = b.phase_cluster(state[0][0], None, moments,
                                          table, active[0])
        return (res_l.member_of[None], res_l.member_sim[None],
                res_l.is_rep[None], res_l.is_outlier[None],
                res_l.alpha_used[None], res_l.k_used[None], diag[None])

    clu_in = ((P(), part1) + ((part2, part2, part1, part1, part1, part1)
                              if plan.sim_mode == "topk" else
                              (part2, part1, part1, part1)))
    clu_out = (part1, part1, part1, part1, P(part_axis), P(part_axis),
               part1)
    # the similarity state (dense [P, S, S] matrix or the top-K lists) is
    # dead once clustered — donate all of it
    clu_donate = tuple(range(2, len(clu_in)))
    cluster_fn = jax.jit(jax.shard_map(
        cluster_body, mesh=mesh, in_specs=clu_in, out_specs=clu_out,
        check_vma=False),
        donate_argnums=clu_donate)

    return {"join": join_fn, "segment": segment_fn,
            "similarity": similarity_fn, "cluster": cluster_fn,
            "refine": jax.jit(refine_stage, donate_argnums=(0, 1, 2, 3))}
