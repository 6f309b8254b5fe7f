"""Single-host end-to-end DSC pipeline (Algorithm 1, P = 1).

This is the semantic reference: the distributed pipeline
(``repro.core.distributed``) must produce the same clusters on the same data
(tested).  The stages mirror the paper exactly:

    subtrajectory join (Problem 1)  ->  voting  ->  segmentation (Problem 2)
    ->  ST / SP relations  ->  clustering + outliers (Problem 3)

Every engine and tile choice (join mode, kernels, grid index, similarity
representation, clustering engine) comes from one
:class:`repro.core.plan.EnginePlan`, whose module docstring says what
each field selects and which path is the parity oracle.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import geometry, segmentation, similarity, voting
from repro.core.clustering import (cluster, rmse, rmse_from_result, sscr,
                                   sscr_from_result)
from repro.core.plan import EnginePlan
from repro.core.types import (ClusteringResult, DSCParams, JoinResult,
                              SubtrajSegmentation, SubtrajTable, TopKSim,
                              TrajectoryBatch)
from repro.utils.programs import run_program
from repro.utils.tree import pytree_dataclass

# stage-state donation is best-effort (see repro.core.distributed): when a
# stage's outputs can't alias a donated buffer XLA still frees it at call
# time; silence the per-compile nag about the unused alias
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@pytree_dataclass
class DSCOutput:
    join: JoinResult | None         # None in fused mode (cube never built)
    vote: jnp.ndarray               # [T, M] point voting
    seg: SubtrajSegmentation
    table: SubtrajTable
    sim: jnp.ndarray | None         # [S, S]; None in sim_mode="topk"
    sim_topk: TopKSim | None        # [S, K] lists in sim_mode="topk"
    sim_overflow: jnp.ndarray | None  # [] i32 certificate violations (topk)
    result: ClusteringResult
    sscr: jnp.ndarray               # Eq. 3 objective
    rmse: jnp.ndarray               # Sec. 6.2 quality metric
    # [2] i32 on the device: the join's matches before and after DTJ's
    # delta_t refine, counted in the index-pruned Pallas join; None at
    # delta_t 0 and on the other paths
    join_matches: jnp.ndarray | None = None


# --------------------------------------------------------------------------
# Stage bodies.  The monolithic jits below AND the per-stage entry points
# (run_stage_*) compose these same functions, so a staged run executes
# literally the same traced code per stage as a straight-through run — that
# code-sharing is the resilient runner's bit-identity argument
# (``repro.run.resilient``, DESIGN.md §10).  Each body runs under its
# stage's ``jax.named_scope`` (``dsc.join``, ``dsc.vote``, ``dsc.segment``,
# ``dsc.similarity``, ``dsc.cluster``, ``dsc.score``), so the device
# operations of every program that composes it carry the stage's name.
# --------------------------------------------------------------------------


def _segment_body(batch, params, vote, masks, plan: EnginePlan):
    """Voting signal -> segmentation -> subtrajectory table."""
    with jax.named_scope("dsc.segment"):
        nvote = voting.normalized_voting(vote, batch.valid)
        if params.segmentation == "tsa1":
            seg = segmentation.tsa1(nvote, batch.valid, params.w,
                                    params.tau, params.max_subtrajs_per_traj)
        else:
            seg = segmentation.tsa2(masks, batch.valid, params.w, params.tau,
                                    params.max_subtrajs_per_traj,
                                    use_kernel=plan.seg_use_kernel)
        table = similarity.build_subtraj_table(
            batch, seg, vote, params.max_subtrajs_per_traj)
    return seg, table


def _pass2(plan: EnginePlan, has_cube: bool) -> str:
    """Which pass 2 builds the raw similarity: ``"cube"``, a Pallas
    kernel that reads the materialized join's kept tiles (the kernel join,
    dense similarity); ``"sweep"``, a Pallas sweep that re-runs the pair
    tests (no cube, or the top-K panels); ``"scatter"``, the XLA scatter
    of the whole cube (``use_kernel=False``)."""
    if not has_cube or (plan.use_kernel and plan.sim_mode == "topk"):
        return "sweep"
    return "cube" if plan.use_kernel else "scatter"


def _similarity_body(batch, params, join, seg, table, plan: EnginePlan,
                     tile_ids=None):
    """SP relation: returns ``(sim, topk)`` — exactly one is non-None.

    Pallas builds the raw scatter whenever the join runs on the kernels
    (:func:`_pass2`): from the join's kept cube tiles in materialize mode,
    by a sweep that recomputes them in fused mode (no cube) and for the
    top-K panels, instead of an XLA scatter of all ``T*M*C`` cube
    entries, whose index operands alone are as large as the cube
    (DESIGN.md §3).  All accumulate every cell in the same order.
    ``tile_ids`` is the sweep's own index plan (:func:`plan_sweep_tiles`).
    """
    with jax.named_scope("dsc.similarity"):
        pass2 = _pass2(plan, join is not None)
        if plan.sim_mode == "topk":
            # sparse SP relation: panel-streamed top-K lists, never [S, S]
            if pass2 == "sweep":
                from repro.kernels.stjoin import ops as stjoin_ops
                Sb = similarity.plan_panel(table.num_slots, plan.sim_panel)

                def panel_raw(p0):
                    return stjoin_ops.stjoin_sim_panel_fused(
                        batch, batch, seg.sub_local, seg.sub_local,
                        params.max_subtrajs_per_traj, params.eps_sp,
                        params.eps_t, params.delta_t, p0=p0, panel=Sb,
                        tile_ids=tile_ids, **_tile_kwargs(plan.fused_tiles))

                topk = similarity.topk_stream(panel_raw, table,
                                              k=plan.sim_topk, panel=Sb)
            else:
                topk = similarity.similarity_topk(
                    join, seg, seg.sub_local, table,
                    params.max_subtrajs_per_traj, k=plan.sim_topk,
                    panel=plan.sim_panel)
            return None, topk

        if pass2 == "cube":
            from repro.kernels.stjoin import ops as stjoin_ops
            raw = stjoin_ops.stjoin_sim_cube(
                join, seg.sub_local, seg.sub_local,
                params.max_subtrajs_per_traj, tile_ids=tile_ids,
                **_tile_kwargs(plan.fused_tiles))
            sim = similarity.finalize_sim(raw, table)
        elif pass2 == "sweep":
            from repro.kernels.stjoin import ops as stjoin_ops
            raw = stjoin_ops.stjoin_sim_fused(
                batch, batch, seg.sub_local, seg.sub_local,
                params.max_subtrajs_per_traj, params.eps_sp, params.eps_t,
                params.delta_t, tile_ids=tile_ids,
                **_tile_kwargs(plan.fused_tiles))
            sim = similarity.finalize_sim(raw, table)
        else:
            sim = similarity.similarity_matrix(
                join, seg, seg.sub_local, table, params.max_subtrajs_per_traj)
        return sim, None


def _cluster_body(simlike, table, params, plan: EnginePlan):
    """Problem 3: returns ``(result, overflow)``; overflow is None for the
    dense path (the certificate only exists for truncated top-K lists)."""
    with jax.named_scope("dsc.cluster"):
        result = cluster(simlike, table, params, engine=plan.cluster_engine,
                         use_kernel=plan.cluster_use_kernel,
                         tiles=plan.cluster_tiles)
        if isinstance(simlike, TopKSim):
            return result, similarity.topk_overflow(simlike, result.alpha_used)
        return result, None


def _score_body(result, sim, params):
    """Quality metrics: moment-based when the dense matrix was skipped."""
    with jax.named_scope("dsc.score"):
        if sim is None:
            return sscr_from_result(result), rmse_from_result(result,
                                                              params.eps_sp)
        return sscr(result, sim), rmse(result, sim, params.eps_sp)


def _finish(batch, params, join, vote, masks, plan: EnginePlan,
            tile_ids=None) -> DSCOutput:
    """Segmentation onward — shared by every join/vote front-end.

    ``plan`` is a resolved :class:`EnginePlan` with a concrete ``sim_topk``
    (the dispatcher clamps K to S before tracing).
    """
    seg, table = _segment_body(batch, params, vote, masks, plan)
    sim, topk = _similarity_body(batch, params, join, seg, table, plan,
                                 tile_ids=tile_ids)
    result, overflow = _cluster_body(topk if topk is not None else sim,
                                     table, params, plan)
    sscr_v, rmse_v = _score_body(result, sim, params)
    return DSCOutput(join=join, vote=vote, seg=seg, table=table, sim=sim,
                     sim_topk=topk, sim_overflow=overflow, result=result,
                     sscr=sscr_v, rmse=rmse_v)


def _vote_from_join_body(params, join):
    with jax.named_scope("dsc.vote"):
        vote = voting.point_voting(join)
        masks = (voting.neighbor_mask_packed(join)
                 if params.segmentation == "tsa2" else None)
        return vote, masks


def _join_vote_materialize_body(batch, params, plan: EnginePlan):
    with jax.named_scope("dsc.join"):
        if plan.use_kernel:
            from repro.kernels.stjoin import ops as stjoin_ops
            join, _ = stjoin_ops.subtrajectory_join(
                batch, batch, params.eps_sp, params.eps_t, params.delta_t)
        else:
            join = geometry.subtrajectory_join(
                batch, batch, params.eps_sp, params.eps_t, params.delta_t,
                use_index=plan.use_index)
    vote, masks = _vote_from_join_body(params, join)
    return join, vote, masks


@functools.partial(jax.jit, static_argnames=("plan",))
def _run_dsc_materialize(batch: TrajectoryBatch, params: DSCParams,
                         plan: EnginePlan) -> DSCOutput:
    join, vote, masks = _join_vote_materialize_body(batch, params, plan)
    return _finish(batch, params, join, vote, masks, plan)


@functools.partial(jax.jit, static_argnames=("plan",), donate_argnums=(2,))
def _run_dsc_from_join(batch: TrajectoryBatch, params: DSCParams,
                       join: JoinResult, tile_ids,
                       plan: EnginePlan) -> DSCOutput:
    """Materializing tail for a join produced outside the jit boundary
    (the host-planned index-pruned Pallas join).  The cube is donated:
    the output hands it back without a second ``[T, M, C]`` copy."""
    vote, masks = _vote_from_join_body(params, join)
    return _finish(batch, params, join, vote, masks, plan,
                   tile_ids=tile_ids)


def _tile_kwargs(fused_tiles):
    """(rows, bc, bm) static tuple -> fused-kernel keyword overrides."""
    if fused_tiles is None:
        return {}
    rows, bc, bm = fused_tiles
    return dict(rows=rows, bc=bc, bm=bm)


def _join_vote_fused_body(batch, params, tile_ids, plan: EnginePlan):
    from repro.kernels.stjoin import ops as stjoin_ops
    with jax.named_scope("dsc.join"):
        return stjoin_ops.stjoin_vote_fused_arrays(
            batch.x, batch.y, batch.t, batch.valid, batch.traj_id,
            batch.x, batch.y, batch.t, batch.valid, batch.traj_id,
            params.eps_sp, params.eps_t, params.delta_t, tile_ids=tile_ids,
            with_masks=params.segmentation == "tsa2",
            **_tile_kwargs(plan.fused_tiles))


@functools.partial(jax.jit, static_argnames=("plan",))
def _run_dsc_fused(batch: TrajectoryBatch, params: DSCParams,
                   tile_ids, plan: EnginePlan) -> DSCOutput:
    vote, masks = _join_vote_fused_body(batch, params, tile_ids, plan)
    return _finish(batch, params, None, vote, masks, plan,
                   tile_ids=tile_ids)


# --------------------------------------------------------------------------
# Per-stage entry points — the checkpointable boundaries of the resilient
# runner (``repro.run.resilient``).  Each jits exactly the body the
# monolithic pipeline runs for that stage, so stage k's output fed into
# stage k+1 reproduces the straight-through run bit for bit.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("plan",))
def run_stage_join(batch: TrajectoryBatch, params: DSCParams,
                   plan: EnginePlan):
    """Materialize-mode stage 1: join cube + votes (+ TSA2 words)."""
    return _join_vote_materialize_body(batch, params, plan)


@functools.partial(jax.jit, static_argnames=("plan",))
def run_stage_join_fused(batch: TrajectoryBatch, params: DSCParams,
                         tile_ids, plan: EnginePlan):
    """Fused-mode stage 1: ``(vote, masks)`` — the cube never exists."""
    return _join_vote_fused_body(batch, params, tile_ids, plan)


@functools.partial(jax.jit, static_argnames=("plan",))
def run_stage_vote_from_join(batch: TrajectoryBatch, params: DSCParams,
                             join: JoinResult, plan: EnginePlan):
    """Stage 1 tail for a host-planned (index-pruned) join."""
    return _vote_from_join_body(params, join)


@functools.partial(jax.jit, static_argnames=("plan",),
                   donate_argnums=(3,))
def run_stage_segment(batch: TrajectoryBatch, params: DSCParams, vote,
                      masks, plan: EnginePlan):
    """Stage 2: segmentation + subtrajectory table from the vote state.
    The packed TSA2 mask cube is donated — it is dead after this stage,
    and the resilient loop holds host copies of all checkpoint state, so
    donation never invalidates a checkpoint reference (DESIGN.md §12)."""
    return _segment_body(batch, params, vote, masks, plan)


@functools.partial(jax.jit, static_argnames=("plan",),
                   donate_argnums=(2,))
def run_stage_similarity(batch: TrajectoryBatch, params: DSCParams, join,
                         seg: SubtrajSegmentation, table: SubtrajTable,
                         tile_ids, plan: EnginePlan):
    """Stage 3: SP relation — ``(sim, topk)``, exactly one non-None.
    ``plan.sim_topk`` must be concrete (clamp K to S before calling).
    The join cube (the largest stage-state buffer) is donated."""
    return _similarity_body(batch, params, join, seg, table, plan,
                            tile_ids=tile_ids)


@functools.partial(jax.jit, static_argnames=("plan",),
                   donate_argnums=(0,))
def run_stage_cluster(simlike, table: SubtrajTable, params: DSCParams,
                      plan: EnginePlan):
    """Stage 4: clustering — ``(result, overflow)``; the similarity
    state is donated (the score stage re-uploads from the host copy)."""
    return _cluster_body(simlike, table, params, plan)


@functools.partial(jax.jit, donate_argnums=(1,))
def run_stage_score(result: ClusteringResult, sim, params: DSCParams):
    """Stage 5 epilogue: ``(sscr, rmse)`` from the clustering state."""
    return _score_body(result, sim, params)


def plan_sweep_tiles(batch: TrajectoryBatch, params: DSCParams,
                     plan: EnginePlan):
    """Host-side tile planning for the fused sweeps under ``use_index``:
    both passes in fused mode, the similarity pass (pass 2) under the
    materialize-mode kernel join.

    Returns ``(tile_ids, plan)`` where the plan has the tile plan's
    resolved geometry bound, so every later sweep uses the exact tiling
    the ids were built for.  ``tile_ids`` is None when no sweep is
    pruned.  Shared by :func:`run_dsc`'s dispatcher and the resilient
    runner — both must plan identically for resume parity.
    """
    if not (plan.use_index and (plan.mode == "fused" or plan.use_kernel)):
        return None, plan
    from repro.kernels.stjoin import ops as stjoin_ops
    tp = stjoin_ops.plan_fused_tiles(
        batch.x, batch.y, batch.t, batch.valid,
        batch.x, batch.y, batch.t, batch.valid,
        params.eps_sp, params.eps_t, **_tile_kwargs(plan.fused_tiles))
    return tp.tile_ids, plan.replace(fused_rows=tp.rows, fused_bc=tp.bc,
                                     fused_bm=tp.bm)


def run_dsc_lowerable(batch: TrajectoryBatch, params: DSCParams,
                      plan: EnginePlan) -> DSCOutput:
    """Trace-friendly single-host pipeline: one plan, one trace.

    The host-level conveniences of :func:`run_dsc` — grid-index planning
    (concrete inputs) and the top-K overflow retry loop (concrete
    ``sim_overflow``) — don't trace, so this entry point skips both: it
    requires ``use_index=False`` and returns the overflow certificate
    instead of retrying.  This is the surface the autotuner
    (``repro.tune.autotune``) lowers, compiles, and times per candidate
    plan, and what anything embedding the pipeline inside a larger jit
    should call.
    """
    plan = (plan or EnginePlan()).validate()
    if plan.use_index:
        raise ValueError("run_dsc_lowerable requires use_index=False "
                         "(index planning is host-driven); use run_dsc")
    S = batch.num_trajs * params.max_subtrajs_per_traj
    k = min(plan.sim_topk if plan.sim_topk is not None else 32, S)
    plan = plan.replace(sim_topk=k)
    if plan.mode == "fused":
        return _run_dsc_fused(batch, params, None, plan)
    return _run_dsc_materialize(batch, params, plan)


def run_dsc(batch: TrajectoryBatch, params: DSCParams, *,
            plan: EnginePlan | None = None,
            on_overflow: str = "widen") -> DSCOutput:
    """Run the full DSC pipeline on one host / one partition.

    ``plan`` holds every per-stage engine and tile choice (``None`` =
    ``EnginePlan()``); :class:`repro.core.plan.EnginePlan` says what each
    field selects.  The call dispatches on it: the fused sweeps, the
    grid-pruned Pallas join (host-side index planning, then the jitted
    tail — concrete inputs only), or the materializing program.

    Under ``sim_mode="topk"`` the spill certificate ``out.sim_overflow``
    is checked on the host after each run, and ``on_overflow`` names the
    policy for a violation (DESIGN.md §10): ``"widen"`` reruns with K
    doubled until it holds (K == S cannot spill), ``"raise"`` raises
    ``RuntimeError`` at once, ``"degrade"`` returns the truncated result
    with the violation recorded in ``out.sim_overflow``.

    The call is the host span ``dsc.run``, with ``T``, ``M``, ``mode``,
    the final ``k``, the ``attempts`` the top-K widen loop made and
    ``pass2``, the similarity's pass 2 (``"cube"``, ``"sweep"`` or
    ``"scatter"``, :func:`_pass2`).
    """
    if on_overflow not in ("raise", "widen", "degrade"):
        raise ValueError(f"on_overflow={on_overflow!r}: expected "
                         "'raise', 'widen', or 'degrade'")
    with TraceAnnotation("dsc.run") as span:
        plan = (plan or EnginePlan()).validate()
        S = batch.num_trajs * params.max_subtrajs_per_traj
        k = min(plan.sim_topk if plan.sim_topk is not None else 32, S)

        def dispatch(k):
            tile_ids, p = plan_sweep_tiles(batch, params,
                                           plan.replace(sim_topk=k))
            if p.mode == "fused":
                return run_program(_run_dsc_fused, batch, params, tile_ids, p)
            if p.use_index and p.use_kernel:
                # grid-pruned Pallas join (delta_t refine in the kernel):
                # host-side planning pass, then jitted tail
                from repro.kernels.stjoin import ops as stjoin_ops
                join, matches = stjoin_ops.subtrajectory_join(
                    batch, batch, params.eps_sp, params.eps_t, params.delta_t,
                    use_index=True)
                out = run_program(_run_dsc_from_join, batch, params, join,
                                  tile_ids, p)
                return out.replace(join_matches=matches)
            return run_program(_run_dsc_materialize, batch, params, p)

        attempts, out = 1, dispatch(k)
        while plan.sim_mode != "dense":
            overflow = int(out.sim_overflow)
            if overflow == 0 or on_overflow == "degrade":
                break
            if k >= S:                  # unreachable: K == S cannot spill
                raise AssertionError("overflow with K == S")
            if on_overflow == "raise":
                raise RuntimeError(
                    f"sim_topk={k} truncated a potential alpha-edge on "
                    f"{overflow} rows (spill >= alpha): labels would not be "
                    "exact.  Raise sim_topk or use on_overflow='widen'.")
            k = min(2 * k, S)
            attempts, out = attempts + 1, dispatch(k)
        if TraceAnnotation.is_enabled():
            span.set_metadata(T=batch.num_trajs, M=batch.x.shape[1],
                              mode=plan.mode, k=k, attempts=attempts,
                              pass2=_pass2(plan, plan.mode == "materialize"))
        return out


def cluster_summary(out: DSCOutput) -> dict:
    """Host-side summary: cluster -> member subtraj slots; outliers list.

    Vectorized numpy grouping (sort-by-owner + unique split) instead of a
    Python loop over every slot — this runs once per evaluation-script
    call, on tables whose slot count grows with T * max_subs.
    """
    import numpy as np
    member_of = np.asarray(out.result.member_of)
    is_rep = np.asarray(out.result.is_rep)
    is_out = np.asarray(out.result.is_outlier)
    valid = np.asarray(out.table.valid)
    owner = np.where(is_rep, np.arange(member_of.shape[0]), member_of)
    slots = np.nonzero(valid & (is_rep | (member_of >= 0)))[0]
    by_owner = slots[np.argsort(owner[slots], kind="stable")]
    reps, starts = np.unique(owner[by_owner], return_index=True)
    clusters: dict[int, list[int]] = {
        int(rep): members.tolist()
        for rep, members in zip(reps, np.split(by_owner, starts[1:]))}
    return {
        "clusters": clusters,
        "outliers": [int(s) for s in np.nonzero(valid & is_out)[0]],
        "num_clusters": len(clusters),
        "sscr": float(out.sscr),
        "rmse": float(out.rmse),
        "alpha": float(out.result.alpha_used),
        "k": float(out.result.k_used),
    }
