"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs pure-jnp refs.

On CPU, interpret mode measures correctness-path overhead, not TPU speed —
the derived column therefore reports work sizes (points x candidates, DP
cells) so TPU projections can be made from the roofline constants.

The dense-vs-pruned stjoin comparison additionally writes
``BENCH_stjoin.json`` (candidate-tile counts, pruning ratio, wall-clock,
bit-parity) so CI can accumulate the perf trajectory as an artifact.
``--smoke`` shrinks every shape for a sub-minute CI run; ``--out-dir``
redirects the JSON.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, time_fn
from repro.core.geometry import best_match_join
from repro.core.types import DSCParams, TrajectoryBatch
from repro.data.synthetic import ais_like
from repro.kernels.jaccard.ops import window_jaccard
from repro.kernels.jaccard.ref import jaccard_ref
from repro.kernels.lcss.ops import lcss_scores
from repro.kernels.lcss.ref import lcss_ref
from repro.kernels.stjoin.ops import (
    best_match_join_kernel,
    best_match_join_pruned,
    stjoin_sim_fused,
    stjoin_vote_fused,
)
from repro.launch.hlo_analysis import (
    find_buffers_with_elements,
    interface_buffer_stats,
    peak_buffer_stats,
)


def _clustered_workload(smoke: bool):
    """Lane-clustered AIS traffic, rows sorted by lane so candidate tiles
    (groups of ``bc`` adjacent rows) stay spatially tight — the regime the
    index is built for."""
    n_vessels, max_points = (16, 32) if smoke else (64, 64)
    batch, labels = ais_like(n_vessels=n_vessels, n_lanes=8,
                             max_points=max_points, area=100.0,
                             lane_width=0.5, seed=1)
    order = np.argsort(labels, kind="stable")
    batch = TrajectoryBatch(
        x=batch.x[order], y=batch.y[order], t=batch.t[order],
        valid=batch.valid[order],
        traj_id=batch.traj_id[order])
    return batch


def bench_stjoin_pruned(smoke: bool = False, out_dir: str = ".") -> dict:
    """Dense vs index-pruned stjoin: tiles, wall-clock, bit-parity."""
    batch = _clustered_workload(smoke)
    eps_sp, eps_t = 3.0, 600.0
    bp, bc, bm = (32, 2, 32) if smoke else (64, 2, 64)

    kw = dict(bp=bp, bc=bc, bm=bm)
    d_secs, (dense, _) = time_fn(best_match_join_kernel, batch, batch,
                                 eps_sp, eps_t, iters=2, **kw)
    p_secs, out = time_fn(best_match_join_pruned, batch, batch,
                          eps_sp, eps_t, iters=2, return_stats=True, **kw)
    (pruned, _), stats = out

    parity = (np.array_equal(np.asarray(dense.best_w),
                             np.asarray(pruned.best_w))
              and np.array_equal(np.asarray(dense.best_idx),
                                 np.asarray(pruned.best_idx)))
    kept = int(stats.kept_tiles)
    rec = {
        "workload": "ais_like clustered (lane-sorted rows)",
        "smoke": bool(smoke),
        "shape": {"T": batch.num_trajs, "M": batch.max_points,
                  "bp": bp, "bc": bc, "bm": bm},
        "eps_sp": eps_sp, "eps_t": eps_t,
        "dense_tiles": stats.dense_tiles,
        "pruned_tiles": kept,
        "pruning_ratio": 1.0 - kept / max(stats.dense_tiles, 1),
        "max_tiles_per_ref_block": int(stats.max_per_ref),
        "dense_us": d_secs * 1e6,
        "pruned_us": p_secs * 1e6,
        "bit_identical": bool(parity),
    }
    csv_row("stjoin_dense", rec["dense_us"],
            f"tiles={rec['dense_tiles']}")
    csv_row("stjoin_pruned", rec["pruned_us"],
            f"tiles={kept};ratio={rec['pruning_ratio']:.3f};"
            f"parity={parity}")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_stjoin.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    assert parity, "pruned join diverged from dense join"
    assert kept < rec["dense_tiles"], \
        "index pruned nothing on the clustered workload"
    return rec


def _cluster_engine_record(sim, table, params, iters: int = 3) -> dict:
    """Sequential-vs-round-parallel timings + parity for one instance."""
    from repro.core.clustering import cluster_rounds, cluster_sequential
    from repro.tune.autotune import measure_compiled
    S = table.num_slots
    res_seq, seq_secs, _ = measure_compiled(
        lambda s, t: cluster_sequential(s, t, params),
        (sim, table), iters=iters)
    (res_rp, rounds), rp_secs, _ = measure_compiled(
        lambda s, t: cluster_rounds(s, t, params, with_rounds=True),
        (sim, table), iters=iters)
    return {
        "S": S,
        "sequential_us": seq_secs * 1e6,
        "rounds_us": rp_secs * 1e6,
        "rounds_executed": int(rounds),
        "sequential_iterations": S,
        "speedup_x": seq_secs / max(rp_secs, 1e-12),
        "label_identical": all(
            bool(np.array_equal(np.asarray(getattr(res_seq, f)),
                                np.asarray(getattr(res_rp, f))))
            for f in ("member_of", "member_sim", "is_rep", "is_outlier")),
    }


def _cluster_gate_instance(S: int = 256, seed: int = 0):
    """Deterministic fixed-shape clustering instance for the CI gate: the
    gate must compare the engines at the same S in smoke and full runs
    (at tiny smoke shapes both engines are dispatch-bound and the
    comparison is noise)."""
    from repro.core.types import SubtrajTable
    rng = np.random.default_rng(seed)
    raw = rng.uniform(0, 1, (S, S)).astype(np.float32)
    sim = np.maximum(raw, raw.T) * (rng.uniform(0, 1, (S, S)) > 0.9)
    np.fill_diagonal(sim, 0.0)
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.asarray(rng.uniform(0, 5, S).astype(np.float32)),
        card=jnp.ones(S, jnp.int32), valid=jnp.ones(S, bool),
        traj_row=jnp.arange(S, dtype=jnp.int32))
    return jnp.asarray(np.maximum(sim, sim.T).astype(np.float32)), table


def _sim_gate_instance(S: int = 512, deg: int = 6, seed: int = 0):
    """Deterministic contribution-level similarity instance for the CI
    gate: ``N = S * deg`` raw SP-scatter contributions with bounded
    per-row degree (so K=32 provably bounds every alpha-degree and the
    certificate stays clean), plus the slot table.  Fixed S so the
    structural memory comparison is made at the same shape in smoke and
    full runs."""
    from repro.core.types import SubtrajTable
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(S), deg)
    dst = rng.integers(0, S, S * deg)
    w = rng.uniform(0.1, 1.0, S * deg).astype(np.float32)
    table = SubtrajTable(
        t_start=jnp.zeros(S), t_end=jnp.ones(S),
        voting=jnp.asarray(rng.uniform(0, 5, S).astype(np.float32)),
        card=jnp.ones(S, jnp.int32), valid=jnp.ones(S, bool),
        traj_row=jnp.arange(S, dtype=jnp.int32))
    return (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(w), table)


def bench_similarity_topk(iters: int = 3) -> dict:
    """Dense [S, S] SP matrix vs panel-streamed top-K lists: wall-clock,
    label identity, the certificate, and the structural memory proof.

    Both paths consume the identical contribution list at the fixed
    S=512 gate shape.  The deterministic gates are bit-identical labels
    with ``overflow == 0``, the absence of any ``[S, S]``-element f32
    buffer in the top-K HLO, and a >=8x peak-buffer reduction for the
    similarity+clustering stages; wall-clock is recorded as trajectory
    data only (CPU timing — the established stance of every gate here).
    """
    from repro.core.clustering import cluster_rounds, cluster_rounds_topk
    from repro.core.similarity import (contribution_panel_raw, finalize_sim,
                                       plan_panel, topk_overflow,
                                       topk_stream)
    from repro.core.types import DSCParams

    src, dst, w, table = _sim_gate_instance()
    S = table.num_slots
    K, Sb = 32, plan_panel(S, 16)
    params = DSCParams(alpha_sigma=0.0, k_sigma=0.0)

    def dense_labels(src, dst, w):
        raw = jnp.zeros((S + 1, S + 1), jnp.float32).at[src, dst].add(w)
        sim = finalize_sim(raw[:S, :S], table)
        return cluster_rounds(sim, table, params)

    def topk_labels(src, dst, w):
        topk = topk_stream(contribution_panel_raw(src, dst, w, S, Sb),
                           table, k=K, panel=Sb)
        res = cluster_rounds_topk(topk, table, params)
        return res, topk_overflow(topk, res.alpha_used)

    dense_fn = jax.jit(dense_labels)
    topk_fn = jax.jit(topk_labels)
    d_secs, res_d = time_fn(dense_fn, src, dst, w, iters=iters)
    t_secs, (res_t, overflow) = time_fn(topk_fn, src, dst, w, iters=iters)

    label_identical = all(
        bool(np.array_equal(np.asarray(getattr(res_d, f)),
                            np.asarray(getattr(res_t, f))))
        for f in ("member_of", "member_sim", "is_rep", "is_outlier"))

    hlo_dense = dense_fn.lower(src, dst, w).compile().as_text()
    hlo_topk = topk_fn.lower(src, dst, w).compile().as_text()
    # the dense fingerprint: any [S, S]- or [S+1, S+1]-element f32 buffer
    fp_topk = (find_buffers_with_elements(hlo_topk, S * S, dtypes=("f32",))
               + find_buffers_with_elements(hlo_topk, (S + 1) * (S + 1),
                                            dtypes=("f32",)))
    fp_dense = (find_buffers_with_elements(hlo_dense, S * S, dtypes=("f32",))
                + find_buffers_with_elements(hlo_dense, (S + 1) * (S + 1),
                                             dtypes=("f32",)))
    peak_dense = peak_buffer_stats(hlo_dense)
    peak_topk = peak_buffer_stats(hlo_topk)

    rec = {
        "shape": {"S": S, "K": K, "panel": Sb,
                  "contributions": int(src.shape[0])},
        "dense_us": d_secs * 1e6,
        "topk_us": t_secs * 1e6,
        "label_identical": bool(label_identical),
        "overflow": int(overflow),
        "dense_fingerprint_in_topk": len(fp_topk),
        "dense_fingerprint_in_dense": len(fp_dense),
        "peak_dense": peak_dense["largest"],
        "peak_topk": peak_topk["largest"],
        "peak_reduction_x": (peak_dense["largest_bytes"]
                             / max(peak_topk["largest_bytes"], 1)),
    }
    csv_row("sim_dense", rec["dense_us"],
            f"peak={peak_dense['largest_bytes']}B")
    csv_row("sim_topk", rec["topk_us"],
            f"peak={peak_topk['largest_bytes']}B;"
            f"identical={label_identical};overflow={rec['overflow']}")
    csv_row("sim_peak_reduction", rec["peak_reduction_x"],
            f"dense={peak_dense['largest_bytes']}B;"
            f"topk={peak_topk['largest_bytes']}B")
    return rec


def _seg_gate_instance(T: int = 32, M: int = 64, W: int = 8, seed: int = 0):
    """Deterministic fixed-shape TSA2 instance for the CI gate: W=8 packed
    words (C=256 candidates) so the structural memory comparison is made
    at the same shape in smoke and full runs."""
    rng = np.random.default_rng(seed)
    masks = jnp.asarray(rng.integers(0, 2 ** 31, (T, M, W)).astype(np.uint32))
    valid = jnp.ones((T, M), bool)
    return masks, valid


def bench_segmentation(w: int = 4, tau: float = 0.2, maxS: int = 8,
                       iters: int = 3) -> dict:
    """Bit-plane vs packed-word TSA2 segmentation: wall-clock, cut-mask
    identity, and the structural memory proof.

    Three signal paths at the fixed W=8 gate shape: the packed windowed-OR
    engine (production), the bit-plane *chunked* fold (the pre-packed
    production path, [T, M, 32] int32 per word-step), and the full
    bit-plane expansion ([T, M, W*32] int32 — the pinned regression
    oracle, what TSA2 costs without packing).  The deterministic gates are
    d/cut identity, the absence of any [T, M, 32]-element int32 buffer in
    the packed HLO, and a >=8x peak-buffer reduction vs the bit-plane
    oracle; wall-clock is recorded as trajectory data only (CPU
    interpret-path timing, same stance as the fused-join and clustering
    gates).
    """
    from repro.core.segmentation import (_windowed_union, tsa2, tsa2_signal)

    masks, valid = _seg_gate_instance()
    T, M, W = masks.shape

    def oracle_signal(m):
        """Full bit-plane expansion, end to end in one graph."""
        n = jnp.arange(m.shape[1])
        l1 = _windowed_union(m, n - w, n - 1)
        l2 = _windowed_union(m, n, n + w - 1)
        inter = jnp.sum(l1 & l2, axis=-1).astype(jnp.float32)
        union = jnp.sum(l1 | l2, axis=-1).astype(jnp.float32)
        return jnp.where(union > 0, 1.0 - inter / jnp.maximum(union, 1.0),
                         0.0)

    packed_fn = jax.jit(lambda m: tsa2_signal(m, w))
    bitplane_fn = jax.jit(lambda m: tsa2_signal(m, w, impl="bitplane"))
    oracle_fn = jax.jit(oracle_signal)

    p_secs, d_packed = time_fn(packed_fn, masks, iters=iters)
    b_secs, d_bitplane = time_fn(bitplane_fn, masks, iters=iters)
    o_secs, d_oracle = time_fn(oracle_fn, masks, iters=iters)
    k_secs, d_kernel = time_fn(window_jaccard, masks, valid, w=w, iters=iters)

    d_identical = (np.array_equal(np.asarray(d_packed),
                                  np.asarray(d_bitplane))
                   and np.array_equal(np.asarray(d_packed),
                                      np.asarray(d_oracle))
                   and np.array_equal(np.asarray(d_packed),
                                      np.asarray(d_kernel)))

    seg_p = tsa2(masks, valid, w, tau, maxS)
    seg_k = tsa2(masks, valid, w, tau, maxS, use_kernel=True)
    cut_identical = all(
        np.array_equal(np.asarray(getattr(seg_p, f)),
                       np.asarray(getattr(seg_k, f)))
        for f in ("cut", "sub_local", "num_subs", "score"))

    def hlo_of(fn):
        return fn.lower(masks).compile().as_text()

    hlo_packed = hlo_of(packed_fn)
    hlo_bitplane = hlo_of(bitplane_fn)
    hlo_oracle = hlo_of(oracle_fn)

    # the 32x expansion fingerprint: a [T, M, 32]-element int32 buffer
    # (one bit-plane chunk) — must be gone from the packed path's HLO
    chunk_elems = T * M * 32
    fp_packed = find_buffers_with_elements(hlo_packed, chunk_elems,
                                           dtypes=("s32",))
    fp_bitplane = find_buffers_with_elements(hlo_bitplane, chunk_elems,
                                             dtypes=("s32",))
    peak_packed = peak_buffer_stats(hlo_packed)
    peak_bitplane = peak_buffer_stats(hlo_bitplane)
    peak_oracle = peak_buffer_stats(hlo_oracle)

    rec = {
        "shape": {"T": T, "M": M, "W": W, "w": w, "C": W * 32},
        "packed_us": p_secs * 1e6,
        "bitplane_chunked_us": b_secs * 1e6,
        "bitplane_oracle_us": o_secs * 1e6,
        "kernel_us": k_secs * 1e6,
        "d_identical": bool(d_identical),
        "cut_identical": bool(cut_identical),
        "bitplane_fingerprint_in_packed": len(fp_packed),
        "bitplane_fingerprint_in_bitplane": len(fp_bitplane),
        "peak_packed": peak_packed["largest"],
        "peak_bitplane_chunked": peak_bitplane["largest"],
        "peak_bitplane_oracle": peak_oracle["largest"],
        "peak_reduction_vs_chunked_x": (
            peak_bitplane["largest_bytes"]
            / max(peak_packed["largest_bytes"], 1)),
        "peak_reduction_x": (peak_oracle["largest_bytes"]
                             / max(peak_packed["largest_bytes"], 1)),
        "interface_packed": interface_buffer_stats(hlo_packed)["largest"],
    }
    csv_row("seg_tsa2_packed", rec["packed_us"],
            f"peak={peak_packed['largest_bytes']}B")
    csv_row("seg_tsa2_bitplane_chunked", rec["bitplane_chunked_us"],
            f"peak={peak_bitplane['largest_bytes']}B")
    csv_row("seg_tsa2_kernel_interpret", rec["kernel_us"],
            f"identical={d_identical}")
    csv_row("seg_peak_reduction", rec["peak_reduction_x"],
            f"oracle={peak_oracle['largest_bytes']}B;"
            f"packed={peak_packed['largest_bytes']}B")
    return rec


# Runs in a subprocess with 8 forced CPU devices: the parent may already
# hold a differently-sized device pool (XLA device counts are fixed at
# backend init), and on a TPU host the parent holds the chip, so the
# child stays on the host CPU explicitly.  It counts HLO bytes, which
# need no chip.  Same idiom as tests/test_distributed.py.
_COMM_DRIVER = r'''
import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np

from repro.core.distributed import build_dsc_stage_programs
from repro.core.partitioning import partition_batch
from repro.core.plan import EnginePlan
from repro.core.types import DSCParams
from repro.data.synthetic import ais_like
from repro.launch.hlo_analysis import collective_inventory

batch, _ = ais_like(n_vessels=64, max_points=48, n_lanes=8, seed=0)
maxS, K = 8, 32
params = DSCParams(eps_sp=3.0, eps_t=600.0, delta_t=0.0, w=4, tau=0.2,
                   alpha_sigma=-1.0, k_sigma=-1.0,
                   max_subtrajs_per_traj=maxS, segmentation="tsa2")
mesh = jax.make_mesh((1, 8), ("part", "model"))
parts = partition_batch(batch, 1)

def summarize(inv):
    return {"by_kind": inv["by_kind"],
            "total_payload_bytes": inv["total_payload_bytes"],
            "peak_payload_bytes": inv["peak_payload_bytes"]}

report = {"shape": {"T": batch.num_trajs, "M": batch.max_points,
                    "S": batch.num_trajs * maxS, "K": K, "mesh": [1, 8]},
          "modes": {}}
labels = {}
for name, hs, se in (("barrier", "barrier", "allgather"),
                     ("ring", "ring", "ring")):
    plan = EnginePlan(sim_mode="topk", sim_topk=K,
                      halo_stream=hs, sim_exchange=se)
    progs = build_dsc_stage_programs(parts, params, mesh, plan=plan)
    p = parts
    pts = (p.x, p.y, p.t, p.valid, p.traj_id, p.ranges)
    join_hlo = progs["join"].lower(*pts).compile().as_text()
    vote, masks, bw, bidx = progs["join"](*pts)
    table, lab = progs["segment"](p.t, p.valid, vote, masks)
    sim_args = pts + (lab, table, bw, bidx)
    sim_hlo = progs["similarity"].lower(*sim_args).compile().as_text()
    report["modes"][name] = {
        "join": summarize(collective_inventory(join_hlo)),
        "similarity": summarize(collective_inventory(sim_hlo)),
    }
    ids, sims, spill, degree, rsum, rsumsq, active = \
        progs["similarity"](*sim_args)
    member, msim, rep, outl, alpha, k, diag = progs["cluster"](
        table, active, ids, sims, spill, degree, rsum, rsumsq)
    final = progs["refine"](member, msim, rep, active, alpha, k)
    labels[name] = tuple(np.asarray(getattr(final, f)).tolist()
                         for f in ("member_of", "is_rep", "is_outlier"))

report["labels_bit_identical"] = labels["barrier"] == labels["ring"]
print("JSON" + json.dumps(report))
'''


def bench_comm() -> dict:
    """Barrier vs ring communication schedules on a forced 8-device mesh.

    Lowers the similarity and join stage programs at the fixed comm gate
    shape (T=64, maxS=8 -> S=512, K=32, mesh 1x8 so the whole ring runs
    on the model axis) under both schedules and inventories every
    collective instruction's payload (``collective_inventory``).  The
    deterministic gates: the ring-mode similarity HLO carries **zero**
    ``all-gather`` / ``all-to-all`` instructions (the exchange is pure
    ``collective-permute`` hops + the psum'd threshold moments), its peak
    per-step payload is at least ``(nM - 1)x`` below the barrier
    schedule's peak gather, and the staged pipeline's final labels are
    bit-identical across schedules.  Wall-clock is not part of this
    record at all — payload bytes are the hardware-independent signal.
    """
    import subprocess
    import sys

    import repro

    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _COMM_DRIVER],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, (
        f"comm driver failed:\n{proc.stdout}\n{proc.stderr}")
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("JSON"))
    rec = json.loads(line[len("JSON"):])

    nM = rec["shape"]["mesh"][1]
    ring_sim = rec["modes"]["ring"]["similarity"]
    barrier_sim = rec["modes"]["barrier"]["similarity"]
    rec["gates"] = {
        "ring_devices": nM,
        "ring_sim_allgather_ops":
            ring_sim["by_kind"].get("all-gather", {}).get("count", 0),
        "ring_sim_alltoall_ops":
            ring_sim["by_kind"].get("all-to-all", {}).get("count", 0),
        "barrier_peak_step_payload_bytes":
            barrier_sim["peak_payload_bytes"],
        "ring_peak_step_payload_bytes": ring_sim["peak_payload_bytes"],
        "peak_step_payload_reduction_x": (
            barrier_sim["peak_payload_bytes"]
            / max(ring_sim["peak_payload_bytes"], 1)),
        "labels_bit_identical": rec["labels_bit_identical"],
    }
    g = rec["gates"]
    csv_row("comm_barrier_peak_step_payload",
            g["barrier_peak_step_payload_bytes"],
            f"total={barrier_sim['total_payload_bytes']}B")
    csv_row("comm_ring_peak_step_payload",
            g["ring_peak_step_payload_bytes"],
            f"total={ring_sim['total_payload_bytes']}B;"
            f"reduction={g['peak_step_payload_reduction_x']:.1f}x;"
            f"identical={g['labels_bit_identical']}")
    assert g["labels_bit_identical"], (
        "ring schedule diverged from the barrier schedule's labels")
    assert g["ring_sim_allgather_ops"] == 0, g
    assert g["ring_sim_alltoall_ops"] == 0, g
    assert g["peak_step_payload_reduction_x"] >= nM - 1, (
        f"ring peak per-step payload reduction "
        f"{g['peak_step_payload_reduction_x']:.1f}x is below the "
        f"(devices - 1) = {nM - 1}x target")
    return rec


def bench_tuning(batch, params, out_dir: str = ".") -> dict:
    """Autotune the tile plans at the pipeline gate shape; record + gate.

    Runs ``repro.tune.autotune.tune_pipeline`` on the same workload the
    pipeline bench gates on, writes the winner store to ``PLANS.json``
    (uploaded next to ``BENCH_pipeline.json`` by CI), and returns the
    ``tuning`` record: per stage, the default plan vs the measured winner
    (wall-clock + peak interface bytes + roofline position), plus the
    merged tuned plan and its end-to-end wall-clock — which makes the
    fused-vs-kernel-path gap a *tracked per-backend measurement* instead
    of a recorded-only flag.  The structural gates (winner verified
    bit-identical to the oracle; winner peak interface bytes <= the
    default plan's) are asserted by the caller; wall-clock is recorded,
    never asserted (CPU interpret-path timing, same stance as every
    other gate here).
    """
    from repro.core.dsc import run_dsc_lowerable
    from repro.core.plan import EnginePlan
    from repro.tune.autotune import PlanStore, measure_compiled, tune_pipeline

    os.makedirs(out_dir, exist_ok=True)
    store = PlanStore(os.path.join(out_dir, "PLANS.json"))
    tuned, results = tune_pipeline(batch, params, store=store)
    store.save()

    # merged-plan end to end: composing the per-stage winners must keep
    # the pipeline's bit-exact label contract
    out_tuned, tuned_wall, _ = measure_compiled(
        lambda b: run_dsc_lowerable(b, params, tuned), (batch,))
    out_default = run_dsc_lowerable(batch, params, EnginePlan())
    merged_identical = all(
        bool(np.array_equal(np.asarray(getattr(out_tuned.result, f)),
                            np.asarray(getattr(out_default.result, f))))
        for f in ("member_of", "is_rep", "is_outlier"))

    def cand(c):
        return {"plan": c.plan.to_dict(), "wall_us": c.wall_s * 1e6,
                "peak_interface_bytes": c.peak_interface_bytes,
                "verified": c.verified, "roofline": c.roofline}

    rec = {
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "plan_store": "PLANS.json",
        "stages": {
            stage: {
                "bucket": r.bucket,
                "num_candidates": len(r.candidates),
                "num_verified": sum(c.verified for c in r.candidates),
                "default": cand(r.default),
                "winner": cand(r.winner),
            } for stage, r in results.items()},
        "tuned_plan": tuned.to_dict(),
        "e2e": {
            "default_us": results["join"].default.wall_s * 1e6,
            "tuned_us": tuned_wall * 1e6,
            "label_identical": bool(merged_identical),
        },
    }
    for stage, s in rec["stages"].items():
        csv_row(f"tune_{stage}_winner", s["winner"]["wall_us"],
                f"peak={s['winner']['peak_interface_bytes']}B;"
                f"default_peak={s['default']['peak_interface_bytes']}B;"
                f"verified={s['num_verified']}/{s['num_candidates']}")
    csv_row("tune_e2e_tuned", rec["e2e"]["tuned_us"],
            f"default={rec['e2e']['default_us']:.0f}us;"
            f"identical={merged_identical}")
    return rec


def bench_pipeline(smoke: bool = False, out_dir: str = ".") -> dict:
    """Fused streaming vs materializing DSC pipeline: per-stage wall-clock,
    peak-allocation estimates, and the join-cube elimination proof.

    Writes ``BENCH_pipeline.json``.  Fails (assert) when the fused path's
    join-stage peak allocation is not strictly below the dense
    ``[T, M, C]`` cube size, when a cube-sized f32/i32 buffer shows up in
    the fused HLO at all, or when the two modes' clustering outputs
    diverge.
    """
    from repro.core import similarity, voting
    from repro.core.dsc import run_dsc_lowerable
    from repro.core.plan import EnginePlan
    from repro.core.segmentation import tsa2
    from repro.kernels.stjoin.ops import subtrajectory_join
    from repro.tune.autotune import measure_compiled

    batch = _clustered_workload(smoke)
    T, M = batch.num_trajs, batch.max_points
    C = T
    eps_sp, eps_t, delta_t = 3.0, 600.0, 0.0
    maxS = 4
    params = DSCParams(eps_sp=eps_sp, eps_t=eps_t, delta_t=delta_t,
                       w=4, tau=0.2, alpha_sigma=-1.0, k_sigma=-1.0,
                       max_subtrajs_per_traj=maxS, segmentation="tsa2")
    # one tile geometry for the staged timings, the end-to-end runs, and
    # the HLO inspection.  Smoke shapes are so small that the library's
    # fat-tile default makes a per-tile block coincide with the cube's
    # element count (bc == C), which would defeat the cube-fingerprint
    # check below — pin a geometry whose blocks cannot collide.
    fkw = dict(rows=8, bc=8, bm=16) if smoke else {}
    fplan = {f"fused_{k}": v for k, v in fkw.items()}

    # ---- per-stage wall-clock ------------------------------------------
    # measure_compiled throughout: one compile, a warm replay excluded,
    # wall = min over timed replays — so the recorded numbers track the
    # steady-state executable, not compile amortization or one-sided
    # scheduler jitter (the old per-call medians moved 2x run to run).
    iters = 3
    stages: dict[str, dict] = {"materialize": {}, "fused": {}}

    join, join_secs, hlo_join = measure_compiled(
        lambda b: subtrajectory_join(b, b, eps_sp, eps_t, delta_t)[0],
        (batch,), iters=iters)
    stages["materialize"]["join"] = join_secs * 1e6
    (vote, masks), c_secs, _ = measure_compiled(
        lambda j: (voting.point_voting(j),
                   voting.neighbor_mask_packed(j)),
        (join,), iters=iters)
    stages["materialize"]["vote+masks"] = c_secs * 1e6

    (f_vote, f_masks), p1_secs, hlo_p1 = measure_compiled(
        lambda b: stjoin_vote_fused(b, b, eps_sp, eps_t, delta_t, **fkw),
        (batch,), iters=iters)
    stages["fused"]["join_pass1"] = p1_secs * 1e6

    seg, seg_secs, _ = measure_compiled(
        lambda m, v: tsa2(m, v, params.w, params.tau, maxS),
        (masks, batch.valid), iters=iters)
    stages["materialize"]["segment"] = stages["fused"]["segment"] = \
        seg_secs * 1e6
    table = similarity.build_subtraj_table(batch, seg, vote, maxS)

    sim_mat, s_secs, _ = measure_compiled(
        lambda j, s, t: similarity.similarity_matrix(
            j, s, s.sub_local, t, maxS),
        (join, seg, table), iters=iters)
    stages["materialize"]["similarity"] = s_secs * 1e6

    def fused_sim(b, sub, t):
        raw = stjoin_sim_fused(b, b, sub, sub, maxS, eps_sp, eps_t,
                               delta_t, **fkw)
        return similarity.finalize_sim(raw, t)
    sim_fused, f_secs, _ = measure_compiled(
        fused_sim, (batch, seg.sub_local, table), iters=iters)
    stages["fused"]["join_pass2+similarity"] = f_secs * 1e6

    # clustering stage: sequential O(S) claim loop vs the round-parallel
    # engine (one entry per engine; both consume the same sim/table).
    # S sequential dependent steps vs O(rounds) [S, S] scans — the CI gate
    # asserts label identity, rounds << S, and a wall-clock win at the
    # fixed gate shape (the pipeline record tracks the workload's own S).
    clustering = _cluster_engine_record(sim_mat, table, params, iters=2)
    stages["materialize"]["cluster"] = clustering["sequential_us"]
    stages["fused"]["cluster"] = clustering["rounds_us"]
    gate_sim, gate_table = _cluster_gate_instance()
    clustering["gate"] = _cluster_engine_record(
        gate_sim, gate_table,
        DSCParams(alpha_sigma=0.0, k_sigma=0.0), iters=3)
    S = clustering["S"]

    # ---- end-to-end + output parity ------------------------------------
    # every variant through the traceable entry (run_dsc_lowerable): no
    # host-side index planning and no top-K overflow retry, so an
    # overflow at the benchmarked K still fails the gate loudly below
    # instead of silently auto-widening past it.  The params are an
    # argument, as in run_dsc's jit: closed over, eps_sp would be a
    # compile-time constant, and XLA on CPU turns the jnp join's division
    # by it into an FMA with the reciprocal, so the oracle would round
    # differently from the kernels (which read eps at run time) and from
    # itself run eagerly (DESIGN.md §3)
    e2e_plans = {
        "materialize_jnp_us": EnginePlan(),
        "materialize_kernel_us": EnginePlan(use_kernel=True),
        "fused_us": EnginePlan(mode="fused", **fplan),
        "seg_kernel_us": EnginePlan(seg_use_kernel=True),
        "topk_us": EnginePlan(sim_mode="topk"),
        "topk_fused_us": EnginePlan(mode="fused", sim_mode="topk", **fplan),
    }
    e2e, e2e_out = {}, {}
    for key, plan in e2e_plans.items():
        e2e_out[key], wall, _ = measure_compiled(
            lambda b, q, p=plan: run_dsc_lowerable(b, q, p),
            (batch, params), iters=iters)
        e2e[key] = wall * 1e6
    out_ref = e2e_out["materialize_jnp_us"]
    out_f = e2e_out["fused_us"]
    out_sk = e2e_out["seg_kernel_us"]
    out_t = e2e_out["topk_us"]
    out_tf = e2e_out["topk_fused_us"]

    # segmentation gate: bit-plane vs packed TSA2 (fixed W=8 instance)
    # plus e2e label/cut identity of the Pallas segmentation kernel path
    segmentation = bench_segmentation(w=params.w, tau=params.tau,
                                      maxS=maxS, iters=2)
    segmentation["e2e_label_identical"] = all(
        bool(np.array_equal(np.asarray(getattr(out_sk.result, f)),
                            np.asarray(getattr(out_ref.result, f))))
        for f in ("member_of", "is_rep", "is_outlier"))
    segmentation["e2e_cut_identical"] = bool(
        np.array_equal(np.asarray(out_sk.seg.cut),
                       np.asarray(out_ref.seg.cut)))

    # similarity gate: dense [S, S] vs panel-streamed top-K lists (fixed
    # S=512 instance) plus e2e label identity of sim_mode="topk" on both
    # execution modes at the pipeline shape
    sim_rec = bench_similarity_topk(iters=2)
    for key, out_x in (("e2e", out_t), ("e2e_fused", out_tf)):
        sim_rec[key + "_label_identical"] = all(
            bool(np.array_equal(np.asarray(getattr(out_x.result, f)),
                                np.asarray(getattr(out_ref.result, f))))
            for f in ("member_of", "member_sim", "is_rep", "is_outlier"))
        sim_rec[key + "_overflow"] = int(out_x.sim_overflow)
        sim_rec[key + "_dense_matrix_dropped"] = out_x.sim is None

    parity = {
        "member_of": bool((np.asarray(out_f.result.member_of)
                           == np.asarray(out_ref.result.member_of)).all()),
        "is_rep": bool((np.asarray(out_f.result.is_rep)
                        == np.asarray(out_ref.result.is_rep)).all()),
        "is_outlier": bool((np.asarray(out_f.result.is_outlier)
                            == np.asarray(out_ref.result.is_outlier)).all()),
        "sim_allclose": bool(np.allclose(np.asarray(out_f.sim),
                                         np.asarray(out_ref.sim),
                                         atol=1e-5)),
        "join_is_none": out_f.join is None,
    }

    # ---- buffer-assignment inspection ----------------------------------
    cube_elems = T * M * C
    cube_bytes = 2 * 4 * cube_elems          # f32 best_w + i32 best_idx

    # hlo_join / hlo_p1 come from the measure_compiled calls above (the
    # identical traces); pass 2 is lowered bare (without finalize_sim) so
    # its interface stats describe the kernel stage alone
    hlo_p2 = jax.jit(lambda b, s: stjoin_sim_fused(
        b, b, s, s, maxS, eps_sp, eps_t, delta_t, **fkw)).lower(
        batch, seg.sub_local).compile().as_text()

    # HBM accounting: interface (parameter + output) buffers are what must
    # cross the stage boundary in HBM; interpret-mode loop temporaries are
    # VMEM scratch on TPU and are reported separately for transparency.
    dense_if = interface_buffer_stats(hlo_join)
    p1_if = interface_buffer_stats(hlo_p1)
    p2_if = interface_buffer_stats(hlo_p2)
    # the join stage proper is pass 1 (votes + packed words); pass 2 is the
    # similarity stage, whose [S+1, S+1] accumulator the materializing path
    # allocates as well — recorded for context, gated on cube absence only
    fused_peak = p1_if["largest_bytes"]
    cube_in_fused = (find_buffers_with_elements(hlo_p1, cube_elems)
                     + find_buffers_with_elements(hlo_p2, cube_elems))
    cube_in_dense = find_buffers_with_elements(hlo_join, cube_elems)

    mem = {
        "cube_bytes": cube_bytes,
        "dense_join_interface_largest": dense_if["largest"],
        "dense_join_interface_total": dense_if["total_bytes"],
        "fused_pass1_interface_largest": p1_if["largest"],
        "fused_pass1_interface_total": p1_if["total_bytes"],
        "fused_pass2_interface_largest": p2_if["largest"],
        "fused_join_peak_bytes": fused_peak,
        "peak_reduction_x": cube_bytes / max(fused_peak, 1),
        "cube_buffers_in_fused_hlo": len(cube_in_fused),
        "cube_buffers_in_dense_hlo": len(cube_in_dense),
        "interpret_scratch_largest": {
            "fused_pass1": peak_buffer_stats(hlo_p1)["largest"],
            "fused_pass2": peak_buffer_stats(hlo_p2)["largest"],
            "dense_join": peak_buffer_stats(hlo_join)["largest"],
        },
    }

    # tile-plan autotuner at the gate shape: default vs measured winners,
    # winners verified bit-identical before acceptance (gated below)
    tuning = bench_tuning(batch, params, out_dir=out_dir)

    # ring vs barrier communication schedules on a forced 8-device mesh
    # (fixed gate shape, run in a subprocess — independent of this
    # process's device pool; gates asserted inside bench_comm and
    # re-asserted from the JSON record by CI)
    comm = bench_comm()

    rec = {
        "workload": "ais_like clustered (lane-sorted rows)",
        "smoke": bool(smoke),
        "note": ("CPU interpret-mode wall-clock; the kernel-backed "
                 "materializing pipeline is the like-for-like comparator "
                 "(same Pallas substrate).  The jnp cube path is recorded "
                 "for reference — it is the implementation the fused mode "
                 "exists to retire at scale."),
        "shape": {"T": T, "M": M, "C": C, "max_subs": maxS, **fkw},
        "eps_sp": eps_sp, "eps_t": eps_t, "delta_t": delta_t,
        "stages_us": stages,
        "end_to_end_us": e2e,
        "fused_not_slower_than_kernel_path": bool(
            e2e["fused_us"] <= e2e["materialize_kernel_us"]),
        "parity": parity,
        "memory": mem,
        "clustering": clustering,
        "segmentation": segmentation,
        "similarity": sim_rec,
        "tuning": tuning,
        "comm": comm,
    }
    for mode, st in stages.items():
        for stage, us in st.items():
            csv_row(f"pipeline_{mode}_{stage}", us)
    csv_row("pipeline_fused_peak_reduction", mem["peak_reduction_x"],
            f"cube={cube_bytes}B;fused_peak={fused_peak}B")
    csv_row("cluster_rounds_engine", clustering["rounds_us"],
            f"rounds={clustering['rounds_executed']}/{S};"
            f"speedup={clustering['speedup_x']:.1f}x;"
            f"identical={clustering['label_identical']}")
    gate = clustering["gate"]
    csv_row("cluster_rounds_gate", gate["rounds_us"],
            f"S={gate['S']};rounds={gate['rounds_executed']};"
            f"speedup={gate['speedup_x']:.1f}x;"
            f"identical={gate['label_identical']}")

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_pipeline.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)

    assert all(parity.values()), f"fused pipeline diverged: {parity}"
    assert not cube_in_fused, (
        f"[T, M, C]-sized f32/i32 buffers in the fused HLO: {cube_in_fused}")
    assert cube_in_dense, (
        "sanity: the materializing join HLO should hold the cube")
    assert fused_peak < cube_bytes, (
        f"fused join-stage peak allocation {fused_peak}B is not strictly "
        f"below the dense cube size {cube_bytes}B")
    assert mem["peak_reduction_x"] >= 8.0, (
        f"fused join-stage peak reduction {mem['peak_reduction_x']:.1f}x "
        "is below the 8x target")
    # Clustering gate.  The hard, deterministic claim is the serial-tail
    # elimination: the sequential engine executes S *dependent* loop
    # iterations, the round engine `rounds_executed` (each a parallel
    # [S, S] sweep).  Wall-clock for both engines is recorded for the
    # perf trajectory but never asserted: at these S both engines run
    # ~1ms on CPU and host timing jitters by 2x+ either way, so any
    # wall-clock bound gates on scheduler noise (same stance as the
    # fused join's recorded-only `fused_not_slower_than_kernel_path`:
    # interpret-path wall-clock is the correctness path, not the
    # hardware signal — the dependent-iteration count is).
    for name, cl in (("pipeline", clustering), ("gate", gate)):
        assert cl["label_identical"], (
            f"round-parallel clustering diverged from the sequential "
            f"oracle on the {name} instance")
        assert cl["rounds_executed"] * 4 <= cl["S"], (
            f"{name}: {cl['rounds_executed']} rounds for S={cl['S']} "
            "slots — not << S")
    assert gate["sequential_iterations"] >= 8 * max(
        gate["rounds_executed"], 1), (
        f"gate: serial-step reduction below 8x: "
        f"{gate['sequential_iterations']} sequential steps vs "
        f"{gate['rounds_executed']} rounds")
    # Segmentation gate.  Deterministic structural claims only: cut/d
    # identity across all three signal paths, no [T, M, 32] int32
    # bit-plane chunk anywhere in the packed HLO, and a >=8x peak-buffer
    # reduction vs the bit-plane oracle at the fixed W=8 gate shape.
    # Wall-clock recorded as trajectory data, never asserted (same
    # stance as the fused-join and clustering gates).
    sg = segmentation
    assert sg["d_identical"], "packed TSA2 signal diverged from bit-plane"
    assert sg["cut_identical"], "TSA2 kernel cuts diverged from jnp engine"
    assert sg["e2e_label_identical"] and sg["e2e_cut_identical"], (
        "seg_use_kernel pipeline diverged from the reference")
    assert sg["bitplane_fingerprint_in_packed"] == 0, (
        f"[T, M, 32] int32 bit-plane chunks in the packed HLO: "
        f"{sg['bitplane_fingerprint_in_packed']}")
    assert sg["bitplane_fingerprint_in_bitplane"] > 0, (
        "sanity: the bit-plane path's HLO should hold the chunk")
    assert sg["peak_reduction_x"] >= 8.0, (
        f"packed segmentation peak-buffer reduction "
        f"{sg['peak_reduction_x']:.1f}x is below the 8x target")
    # Similarity gate.  Deterministic structural claims only: bit-identical
    # labels with a clean spill certificate (gate instance + both e2e
    # modes), no [S, S]-element f32 buffer anywhere in the top-K HLO, and
    # a >=8x peak-buffer reduction for the similarity+clustering stages at
    # the fixed S=512 gate shape.  Wall-clock recorded, never asserted
    # (same stance as every other gate).
    sr = sim_rec
    assert sr["label_identical"] and sr["overflow"] == 0, sr
    assert sr["e2e_label_identical"] and sr["e2e_overflow"] == 0, sr
    assert sr["e2e_fused_label_identical"] and sr["e2e_fused_overflow"] == 0, sr
    assert sr["e2e_dense_matrix_dropped"], sr
    assert sr["e2e_fused_dense_matrix_dropped"], sr
    assert sr["dense_fingerprint_in_topk"] == 0, (
        f"[S, S]-element f32 buffers in the top-K HLO: "
        f"{sr['dense_fingerprint_in_topk']}")
    assert sr["dense_fingerprint_in_dense"] > 0, (
        "sanity: the dense similarity HLO should hold the matrix")
    assert sr["peak_reduction_x"] >= 8.0, (
        f"top-K similarity peak-buffer reduction "
        f"{sr['peak_reduction_x']:.1f}x is below the 8x target")
    # Tuning gate.  Deterministic structural claims only: every stage
    # winner survived bit-identity verification against its engine
    # oracle, no winner is worse than the default plan on peak interface
    # bytes (candidate 0 IS the default, so this can only fail if the
    # sweep's ranking broke), and the merged tuned plan reproduces the
    # default plan's labels end to end.  Wall-clock recorded, never
    # asserted (same stance as every other gate).
    for stage, st in tuning["stages"].items():
        assert st["winner"]["verified"], (
            f"tuning[{stage}]: unverified winner accepted")
        assert (st["winner"]["peak_interface_bytes"]
                <= st["default"]["peak_interface_bytes"]), (
            f"tuning[{stage}]: winner peak interface bytes "
            f"{st['winner']['peak_interface_bytes']} exceed the default "
            f"plan's {st['default']['peak_interface_bytes']}")
    assert tuning["e2e"]["label_identical"], (
        "merged tuned plan diverged from the default plan's labels")
    return rec


def run(smoke: bool = False, out_dir: str = "."):
    if smoke:
        batch, _ = ais_like(n_vessels=8, max_points=32, seed=1)
    else:
        batch, _ = ais_like(n_vessels=32, max_points=64, seed=1)
    eps_sp, eps_t = 3.0, 180.0

    secs, _ = time_fn(best_match_join, batch, batch, eps_sp, eps_t, iters=2)
    work = batch.num_trajs * batch.max_points * batch.num_trajs
    csv_row("stjoin_ref_jnp", secs * 1e6, f"pairs={work}")
    secs, _ = time_fn(best_match_join_kernel, batch, batch, eps_sp, eps_t,
                      iters=2)
    csv_row("stjoin_pallas_interpret", secs * 1e6, f"pairs={work}")

    bench_stjoin_pruned(smoke=smoke, out_dir=out_dir)
    bench_pipeline(smoke=smoke, out_dir=out_dir)

    rng = np.random.default_rng(0)
    B, N, M = (2, 32, 32) if smoke else (8, 64, 64)
    mk = lambda shape: jnp.asarray(rng.normal(0, 3, shape), jnp.float32)
    rx, ry = mk((B, N)), mk((B, N))
    rt = jnp.asarray(np.sort(rng.uniform(0, 500, (B, N)), 1), jnp.float32)
    sx, sy = mk((B, M)), mk((B, M))
    st = jnp.asarray(np.sort(rng.uniform(0, 500, (B, M)), 1), jnp.float32)
    ones = jnp.ones((B, N), bool)
    secs, _ = time_fn(lcss_ref, rx, ry, rt, ones, sx, sy, st, ones,
                      2.0, 60.0, iters=2)
    csv_row("lcss_ref_jnp", secs * 1e6, f"dp_cells={B*N*M}")
    secs, _ = time_fn(lcss_scores, rx, ry, rt, ones, sx, sy, st, ones,
                      2.0, 60.0, iters=2)
    csv_row("lcss_pallas_interpret", secs * 1e6, f"dp_cells={B*N*M}")

    T, Mm, W, w = (4, 32, 2, 4) if smoke else (16, 128, 4, 8)
    masks = jnp.asarray(rng.integers(0, 2**31, (T, Mm, W)).astype(np.uint32))
    valid = jnp.ones((T, Mm), bool)
    secs, _ = time_fn(jaccard_ref, masks, w, iters=2)
    csv_row("jaccard_ref_jnp", secs * 1e6, f"positions={T*Mm};bits={W*32}")
    secs, _ = time_fn(window_jaccard, masks, valid, w=w, iters=2)
    csv_row("jaccard_pallas_interpret", secs * 1e6,
            f"positions={T*Mm};bits={W*32}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the CI smoke job")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_*.json records")
    ns = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(smoke=ns.smoke, out_dir=ns.out_dir)
