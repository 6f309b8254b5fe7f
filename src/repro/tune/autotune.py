"""Tile-plan autotuner: sweep EnginePlan geometries, verify, cache winners.

Closes the ROADMAP's "tile-plan autotuner" item: every Pallas engine in
this repo runs at hand-picked block shapes (fused join ``(rows, bc, bm)``,
clustering ``(bu, bs)``, similarity panel ``Sb`` and list width ``K``),
and which shape wins is a property of the backend and the workload shape
— not something to hardcode.  The tuner makes the choice measured,
verified, and cached:

* **One trace per geometry.**  Tile geometry rides through ``jax.jit``
  static arguments (a frozen :class:`~repro.core.plan.EnginePlan` IS the
  static key), so each candidate costs exactly one ``lower().compile()``
  plus timed replays of the compiled executable.  That invariant — built
  into every engine since PR 2 — is what makes a sweep affordable: N
  candidates cost N compiles, never N recompiles per call site.
* **Verify before accept.**  A candidate only becomes eligible after its
  output is bit-identical to the stage's engine oracle (final labels for
  the end-to-end join sweep, the jnp reference for the cluster kernels,
  the dense ``topk_reduce_rows`` for the panel sweep).  Tile geometry
  must never buy speed with different answers; a geometry that shifts
  f32 summation enough to flip a label is *rejected*, not ranked.
* **Deterministic winner.**  Candidates are ranked by peak
  interface-buffer bytes (``launch.hlo_analysis.interface_buffer_stats``
  — the honest cross-stage HBM footprint) with wall-clock and candidate
  order only breaking ties.  The default plan is always candidate 0, so
  a tuned plan can never regress the primary key — the property the
  ``tuning`` gate in ``BENCH_pipeline.json`` asserts.
* **Cached per (shape-bucket, backend, jax version).**  Winners land in a
  JSON :class:`PlanStore` keyed by ``stage|bucket|backend|jaxN``: shapes
  bucket to powers of two (a sweep at S=512 serves S=300..512), backends
  tune independently (CPU interpret mode and TPU rank geometries
  differently), and a jax upgrade invalidates the cache rather than
  silently replaying stale winners.

Each candidate record also carries peak-buffer bytes and its roofline
position (``benchmarks.roofline.roofline_position`` over the analyzed
HLO) so a stored plan explains *why* it won, not just that it did.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import EnginePlan
from repro.launch.hlo_analysis import (analyze_hlo, interface_buffer_stats,
                                       peak_buffer_stats)

_LABEL_FIELDS = ("member_of", "is_rep", "is_outlier")


# --------------------------------------------------------------------------
# cache keys and the plan store
# --------------------------------------------------------------------------

def shape_bucket(**dims) -> str:
    """Deterministic shape-bucket string: each dim rounded up to a power
    of two (``T=24 -> T32``), keys sorted.  A sweep tuned at the bucket
    ceiling serves every shape in the bucket — tile validity and relative
    ranking are stable within a 2x band, and exact-shape keys would make
    the cache miss on every workload."""
    parts = []
    for k in sorted(dims):
        v = int(dims[k])
        parts.append(f"{k}{1 if v <= 1 else 2 ** math.ceil(math.log2(v))}")
    return "-".join(parts)


def plan_cache_key(stage: str, bucket: str, backend: str | None = None,
                   jax_version: str | None = None) -> str:
    """``stage|bucket|backend|jaxVERSION`` — the PlanStore key."""
    backend = backend or jax.default_backend()
    jax_version = jax_version or jax.__version__
    return f"{stage}|{bucket}|{backend}|jax{jax_version}"


class PlanStore:
    """JSON store of tuned plans: cache key -> winner record.

    ``get`` returns the cached :class:`EnginePlan` (or None);
    ``put`` records a :class:`TuneResult`'s winner; ``save`` writes the
    whole store (winner plan + per-candidate audit trail) to ``path``.
    """

    def __init__(self, path: str | None = None):
        self.path = path
        self.records: dict[str, dict] = {}
        if path is not None and os.path.exists(path):
            with open(path) as f:
                self.records = json.load(f)

    def get(self, stage: str, bucket: str, **key_kw) -> EnginePlan | None:
        rec = self.records.get(plan_cache_key(stage, bucket, **key_kw))
        return None if rec is None else EnginePlan.from_dict(rec["plan"])

    def put(self, result: "TuneResult", **key_kw) -> str:
        key = plan_cache_key(result.stage, result.bucket, **key_kw)
        self.records[key] = result.to_dict()
        return key

    def save(self, path: str | None = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("PlanStore has no path")
        with open(path, "w") as f:
            json.dump(self.records, f, indent=2, sort_keys=True)
            f.write("\n")
        return path


# --------------------------------------------------------------------------
# measurement: one trace per geometry
# --------------------------------------------------------------------------

def measure_compiled(fn, args, iters: int = 1):
    """(out, wall_s, hlo_text): compile ``fn(*args)`` once, replay timed.

    One ``lower().compile()`` per call — the tuner's entire compile cost
    for a candidate.  The first replay warms the executable (excluded);
    ``wall_s`` is the minimum over ``iters`` timed replays (minimum, not
    median: replay noise is one-sided).
    """
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    out = jax.block_until_ready(compiled(*args))
    wall = math.inf
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        wall = min(wall, time.perf_counter() - t0)
    return out, wall, hlo


def _roofline(hlo: str) -> dict | None:
    """Roofline position of an analyzed HLO, or None when
    ``benchmarks.roofline`` is not importable (installed-package use —
    the benchmarks tree ships with the repo, not the wheel)."""
    try:
        from benchmarks.roofline import roofline_position
    except ImportError:
        return None
    a = analyze_hlo(hlo)
    hbm = a["hbm_traffic_fused_bytes"] or a["hbm_traffic_bytes"]
    return roofline_position(a["flops"], hbm, a["collective_bytes"])


# --------------------------------------------------------------------------
# the sweep
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CandidateRecord:
    plan: EnginePlan
    wall_s: float
    verified: bool
    peak_interface_bytes: int
    peak_buffer_bytes: int
    roofline: dict | None
    note: str = ""

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["plan"] = self.plan.to_dict()
        d["wall_s"] = None if math.isinf(self.wall_s) else self.wall_s
        return d


@dataclasses.dataclass
class TuneResult:
    stage: str
    bucket: str
    candidates: list[CandidateRecord]
    default: CandidateRecord        # candidates[0] — the untuned baseline
    winner: CandidateRecord

    def to_dict(self) -> dict:
        return {
            "stage": self.stage, "bucket": self.bucket,
            "backend": jax.default_backend(), "jax": jax.__version__,
            "plan": self.winner.plan.to_dict(),
            "winner": self.winner.to_dict(),
            "default": self.default.to_dict(),
            "candidates": [c.to_dict() for c in self.candidates],
        }


def sweep(stage: str, bucket: str, candidates, measure, verify,
          store: PlanStore | None = None, **store_kw) -> TuneResult:
    """Measure every candidate plan once, verify, pick the winner.

    ``measure(plan) -> (out, wall_s, hlo_text)`` is the one-trace
    measurement; ``verify(out, plan) -> bool`` is the bit-identity check
    against the stage oracle.  Both are injectable so tests can pin a
    fixed candidate set (determinism) or plant a deliberately-wrong
    candidate (rejection).  Candidate 0 must be the stage's default plan;
    a candidate whose measurement *raises* (invalid geometry) is recorded
    as unverified rather than aborting the sweep.  Winner = the verified
    candidate minimizing ``(peak_interface_bytes, wall_s, index)`` —
    fully deterministic given the measurements.
    """
    records: list[CandidateRecord] = []
    for plan in candidates:
        try:
            out, wall, hlo = measure(plan)
        except Exception as e:  # noqa: BLE001 — geometry rejected, not fatal
            records.append(CandidateRecord(
                plan=plan, wall_s=math.inf, verified=False,
                peak_interface_bytes=-1, peak_buffer_bytes=-1,
                roofline=None, note=f"measure failed: {e}"))
            continue
        ok = bool(verify(out, plan))
        records.append(CandidateRecord(
            plan=plan, wall_s=wall, verified=ok,
            peak_interface_bytes=interface_buffer_stats(hlo)["largest_bytes"],
            peak_buffer_bytes=peak_buffer_stats(hlo)["largest_bytes"],
            roofline=_roofline(hlo),
            note="" if ok else "rejected: not bit-identical to the oracle"))
    eligible = [(r.peak_interface_bytes, r.wall_s, i)
                for i, r in enumerate(records) if r.verified]
    if not eligible:
        raise RuntimeError(
            f"tune[{stage}]: no candidate survived verification "
            f"({[r.note for r in records]})")
    winner = records[min(eligible)[2]]
    result = TuneResult(stage=stage, bucket=bucket, candidates=records,
                        default=records[0], winner=winner)
    if store is not None:
        store.put(result, **store_kw)
    return result


def _labels_equal(res_a, res_b) -> bool:
    return all(np.array_equal(np.asarray(getattr(res_a, f)),
                              np.asarray(getattr(res_b, f)))
               for f in _LABEL_FIELDS)


# --------------------------------------------------------------------------
# stage drivers
# --------------------------------------------------------------------------

def join_candidates(T: int, M: int, base: EnginePlan) -> list[EnginePlan]:
    """Join-stage candidate lattice for a ``[T, M]`` self-join.

    Candidate 0 is ``base`` untouched (the library default — on the
    default plan that is the materializing oracle, so the sweep measures
    the cube path and the fused geometries side by side and the recorded
    wall-clocks ARE the fused-vs-kernel-path gap, per backend).  The rest
    are fused plans on a small deterministic ``(rows, bc, bm)`` lattice
    around the fat-tile default; ``plan_fused_tiles``-style clamping
    happens inside the kernels, so duplicates after clamping are dropped
    here by their pre-clamp key only.
    """
    cands = [base]
    seen = set()

    def add(rows, bc, bm):
        rows = None if rows is None else max(1, min(int(rows), T))
        bm = max(8, min(int(bm), M))
        key = (rows, bc, bm)
        if key in seen:
            return
        seen.add(key)
        cands.append(base.replace(mode="fused", fused_rows=rows,
                                  fused_bc=bc, fused_bm=bm))

    add(None, 128, 128)                      # the fused library default
    auto_rows = max(1, 256 // max(M, 1))
    # lane tiles a TPU accepts: a multiple of 128 (clamped to every
    # candidate when there are fewer)
    for rows in (1, 4, auto_rows):
        for bc, bm in ((128, 64), (128, 128), (256, 32)):
            if len(cands) >= 8:
                return cands
            add(rows, bc, bm)
    return cands


def tune_join(batch, params, base: EnginePlan | None = None,
              candidates: list[EnginePlan] | None = None,
              store: PlanStore | None = None, iters: int = 1,
              oracle=None) -> TuneResult:
    """Tune join mode + fused tile geometry by running the whole pipeline.

    Measurement is end-to-end (``run_dsc_lowerable``) on purpose: one
    trace per candidate covers timing, HLO inspection, AND verification
    output, and the interface-buffer key then reflects what the geometry
    actually changes — whether the ``[T, M, C]`` cube crosses a stage
    boundary, and how much tile padding the fused sweeps carry.
    Verification is final-label bit-identity against the materializing
    oracle (fused vote/sim values are only allclose across geometries —
    f32 summation order — but labels are the pipeline's bit-exact
    contract, and a geometry that flips one is rejected).
    """
    from repro.core.dsc import run_dsc_lowerable
    T, M = batch.x.shape
    base = (base or EnginePlan()).validate()
    if candidates is None:
        candidates = join_candidates(T, M, base)

    def measure(plan):
        return measure_compiled(
            lambda b: run_dsc_lowerable(b, params, plan), (batch,),
            iters=iters)

    oracle_res = oracle if oracle is not None else \
        measure_compiled(lambda b: run_dsc_lowerable(
            b, params, base.replace(mode="materialize")), (batch,))[0]

    def verify(out, plan):
        return _labels_equal(out.result, oracle_res.result)

    return sweep("join", shape_bucket(T=T, M=M), candidates,
                 measure, verify, store=store)


def cluster_candidates(S: int, base: EnginePlan) -> list[EnginePlan]:
    """Cluster-stage candidates: the base engine untouched (candidate 0 —
    jnp unless the base plan already picked the kernels), then the Pallas
    round kernels over a small (bu, bs) tile lattice."""
    cands = [base]
    for bu, bs in ((8, 128), (8, 64), (16, 128), (8, 256), (16, 64)):
        plan = base.replace(cluster_engine="rounds",
                            cluster_use_kernel=True,
                            cluster_bu=bu, cluster_bs=bs)
        if plan not in cands:
            cands.append(plan)
    return cands


def tune_cluster_tiles(sim, table, params, base: EnginePlan | None = None,
                       candidates: list[EnginePlan] | None = None,
                       store: PlanStore | None = None,
                       iters: int = 1) -> TuneResult:
    """Tune the Problem 3 engine + round-kernel tiles on a dense instance.

    Oracle: the jnp round engine (bit-identical to the sequential
    transcription by the PR 3 contract).  The Pallas kernels are
    bit-identical to it for any tile geometry — padding only adds slots
    that join no reduction — so verification here compares ALL result
    fields, not just labels, and any geometry that breaks the padding
    invariant is rejected.
    """
    from repro.core.clustering import cluster_rounds
    S = int(table.num_slots)
    base = (base or EnginePlan()).validate()
    if candidates is None:
        candidates = cluster_candidates(S, base)

    def fn_for(plan):
        return lambda s, t: cluster_rounds(
            s, t, params, use_kernel=plan.cluster_use_kernel,
            tiles=plan.cluster_tiles)

    oracle_res = measure_compiled(
        lambda s, t: cluster_rounds(s, t, params), (sim, table))[0]

    def measure(plan):
        return measure_compiled(fn_for(plan), (sim, table), iters=iters)

    def verify(out, plan):
        return all(np.array_equal(np.asarray(getattr(out, f)),
                                  np.asarray(getattr(oracle_res, f)))
                   for f in ("member_of", "member_sim", "is_rep",
                             "is_outlier", "alpha_used", "k_used"))

    return sweep("cluster", shape_bucket(S=S), candidates,
                 measure, verify, store=store)


def panel_candidates(S: int, base: EnginePlan) -> list[EnginePlan]:
    """Similarity-stage candidates: the base panel (candidate 0), then a
    small Sb ladder.  ``plan_panel`` snaps each target to the largest
    divisor of S, so targets that collapse to the same Sb dedupe here."""
    from repro.core.similarity import plan_panel
    cands, seen = [], set()
    for target in (base.sim_panel, 32, 64, 128, 256):
        Sb = plan_panel(S, target)
        if Sb in seen:
            continue
        seen.add(Sb)
        cands.append(base.replace(sim_mode="topk", sim_panel=Sb))
    return cands


def tune_sim_panel(src, dst, w, table, params,
                   base: EnginePlan | None = None,
                   candidates: list[EnginePlan] | None = None,
                   store: PlanStore | None = None,
                   iters: int = 1) -> TuneResult:
    """Tune the top-K panel height Sb on a contribution-list instance.

    Oracle: the dense path — scatter, ``finalize_sim``, then one
    ``topk_reduce_rows`` over full rows.  The streamed panel sweep is
    bitwise-equal to it for EVERY divisor Sb (PR 5's fixed pairwise-tree
    contract), so verification compares ids, sims, the spill certificate,
    and the threshold moments bit for bit; a panel height that breaks the
    tree invariant is rejected.
    """
    from repro.core.similarity import (contribution_panel_raw, finalize_sim,
                                       sim_row_moments, topk_reduce_rows,
                                       topk_stream)
    S = int(table.num_slots)
    base = (base or EnginePlan()).replace(sim_mode="topk")
    K = min(base.sim_topk if base.sim_topk is not None else 32, S)
    base = base.replace(sim_topk=K)
    if candidates is None:
        candidates = panel_candidates(S, base)

    def dense_oracle(src, dst, w):
        raw = jnp.zeros((S + 1, S + 1), jnp.float32).at[src, dst].add(w)
        sim = finalize_sim(raw[:S, :S], table)
        ids, sims, spill = topk_reduce_rows(sim, K)
        cnt, rsum, rsumsq = sim_row_moments(sim, table.valid, table.valid)
        return ids, sims, spill, cnt, rsum, rsumsq

    o_ids, o_sims, o_spill, o_cnt, o_sum, o_sumsq = measure_compiled(
        dense_oracle, (src, dst, w))[0]

    def measure(plan):
        def fn(src, dst, w):
            return topk_stream(
                contribution_panel_raw(src, dst, w, S, plan.sim_panel),
                table, k=K, panel=plan.sim_panel)
        return measure_compiled(fn, (src, dst, w), iters=iters)

    def verify(topk, plan):
        pairs = ((topk.ids, o_ids), (topk.sims, o_sims),
                 (topk.spill, o_spill), (topk.degree, o_cnt),
                 (topk.row_sum, o_sum), (topk.row_sumsq, o_sumsq))
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in pairs)

    return sweep("similarity", shape_bucket(S=S, K=K), candidates,
                 measure, verify, store=store)


def comm_candidates(base: EnginePlan) -> list[EnginePlan]:
    """Comm-schedule candidates (DESIGN.md §12): candidate 0 is the
    barrier baseline — the tuner's no-regression anchor — then each ring
    schedule alone, then both.  Unlike tile geometries, ring and barrier
    schedules are bit-identical by construction, so a verification
    failure here is a real bug, not a rejected candidate."""
    barrier = base.replace(halo_stream="barrier", sim_exchange="allgather")
    cands = [barrier]
    for hs, se in (("ring", "allgather"), ("barrier", "ring"),
                   ("ring", "ring")):
        p = barrier.replace(halo_stream=hs, sim_exchange=se)
        if p not in cands:
            cands.append(p)
    return cands


def tune_comm(parts, params, mesh, *, part_axis: str = "part",
              model_axis: str = "model", base: EnginePlan | None = None,
              candidates: list[EnginePlan] | None = None,
              store: PlanStore | None = None, iters: int = 1) -> TuneResult:
    """Tune the distributed communication schedules on a live mesh.

    Each candidate compiles the full distributed monolith once
    (``halo_stream`` / ``sim_exchange`` are plan fields, hence jit static
    keys — one trace per schedule).  Verification is final-label
    bit-identity against candidate 0, the barrier baseline: a ring
    schedule is a pure reordering of the same data movement, so anything
    short of bit-identical labels is rejected.  The interface-bytes
    primary key ties across schedules (the program boundary is
    unchanged); wall-clock — where overlap actually shows up — breaks
    the tie, with candidate order keeping the result deterministic.
    """
    from repro.core.distributed import build_dsc_program
    base = (base or EnginePlan()).validate()
    if candidates is None:
        candidates = comm_candidates(base)
    args = (parts.x, parts.y, parts.t, parts.valid, parts.traj_id,
            parts.ranges)

    def measure(plan):
        prog = build_dsc_program(parts, params, mesh, part_axis=part_axis,
                                 model_axis=model_axis, plan=plan)
        return measure_compiled(prog, args, iters=iters)

    oracle_final = measure(candidates[0])[0][0]

    def verify(out, plan):
        return _labels_equal(out[0], oracle_final)

    bucket = shape_bucket(T=parts.x.shape[1], P=mesh.shape[part_axis],
                          M=mesh.shape[model_axis])
    return sweep("comm", bucket, candidates, measure, verify, store=store)


def tune_pipeline(batch, params, base: EnginePlan | None = None,
                  store: PlanStore | None = None,
                  iters: int = 1):
    """(tuned plan, {stage: TuneResult}): tune all three swept stages.

    The join sweep runs end to end on ``batch``; the cluster sweep reuses
    the join oracle's dense similarity + slot table as its instance (the
    real downstream inputs at this shape); the panel sweep runs on the
    positive entries of that matrix as a contribution list.  The merged
    plan takes each stage's winner fields — they compose freely because
    every stage's geometry knob is independent by construction.
    """
    from repro.core.dsc import run_dsc_lowerable
    base = (base or EnginePlan()).validate()
    oracle = measure_compiled(
        lambda b: run_dsc_lowerable(b, params,
                                    base.replace(mode="materialize")),
        (batch,))[0]
    results = {
        "join": tune_join(batch, params, base=base, store=store,
                          iters=iters, oracle=oracle)}

    sim, table = oracle.sim, oracle.table
    results["cluster"] = tune_cluster_tiles(sim, table, params, base=base,
                                            store=store, iters=iters)

    S = int(table.num_slots)
    sim_np = np.asarray(sim)
    src_np, dst_np = np.nonzero(sim_np)
    contribs = (jnp.asarray(src_np, jnp.int32),
                jnp.asarray(dst_np, jnp.int32),
                jnp.asarray(sim_np[src_np, dst_np], jnp.float32))
    results["similarity"] = tune_sim_panel(*contribs, table, params,
                                           base=base, store=store,
                                           iters=iters)

    cw = results["cluster"].winner.plan
    sw = results["similarity"].winner.plan
    tuned = results["join"].winner.plan.replace(
        cluster_engine=cw.cluster_engine,
        cluster_use_kernel=cw.cluster_use_kernel,
        cluster_bu=cw.cluster_bu, cluster_bs=cw.cluster_bs,
        sim_panel=sw.sim_panel)
    return tuned, results
