"""Windowed incremental DSC: the long-running streaming service core.

The :class:`StreamDriver` keeps one *active window* of the stream — a
fixed-capacity ``[T, M]`` trajectory store over event times in
``[watermark - horizon, +inf)`` — plus the standing derived state of the
whole DSC pipeline over that window:

* the join **cube** ``best_w/best_idx [T, M, T]`` (DTJ output with the
  window itself as candidate set),
* per-point voting and segmentation (``sub_local``) and the ST relation
  (:class:`~repro.core.types.SubtrajTable`) over ``S = T * max_subs``
  slots,
* **standing neighbor lists** ``[S, K+1]`` — the canonical top-``K+1``
  of the window's similarity panel (the +1 column is the spill that
  feeds the exactness certificate),
* cluster labels from warm-started round-parallel Algorithm 4.

Incrementality contract (DESIGN.md §13.4)
-----------------------------------------
Every window advance computes exactly what a from-scratch batch run over
the current window contents would: the delta path is a *performance*
strategy, never an approximation.  Per advance:

1. admitted records are inserted time-sorted into their object's row;
   the set of touched rows is **dirty**;
2. eviction (event time < ``watermark - horizon``) left-packs rows and
   extends the dirty set;
3. only dirty rows get fresh bounding boxes and a delta join — dirty
   rows vs the whole window (forward) and the whole window vs dirty
   rows (reverse), bbox-pruned by :func:`exact_pair_mask`.  Scattered
   into the cube these reproduce the full batch join bit for bit:
   each ``(r, m, c)`` cell is a pure function of row ``r`` and row
   ``c``'s points, so recomputing the dirty cross sections and keeping
   the clean x clean block is exact;
4. voting / segmentation / ST rebuild from the cube (cheap, [T, M]);
   rows whose segmentation changed join the dirty set for similarity;
5. a **fresh block** recomputes the dirty slots' similarity rows and
   columns from the cube; standing lists merge it: dirty rows are
   replaced outright, clean rows purge dirty/invalid neighbors and
   fold the fresh *column* candidates back in via the canonical
   ``sort_topk_lists`` merge (a set function — order-independent);
6. a clean row whose list was full before the purge and whose new
   ``K+1``-th value does not exceed the old one may have lost mass it
   can no longer prove it never needed: such **stale** rows are
   recomputed outright in a second fresh-block pass *within the same
   advance* (pass 2 purges nothing, so no cascade — two passes always
   suffice).  Standing lists therefore equal the batch top-``K+1`` of
   the current window at every advance boundary, bit for bit;
7. clustering warm-starts: slots whose visit rank, potential flag and
   neighbor list all survived unchanged — the prefix ``[0, r*)`` of the
   visit order — are seeded as already-resolved with their previous
   rep/member verdicts (valid because a slot's verdict in Algorithm 4
   depends only on earlier-ranked slots; requires the *absolute*
   thresholds StreamConfig enforces, so alpha/k cannot drift with
   window statistics);
8. every ``snapshot_every`` advances the full state snapshots through
   :class:`~repro.checkpoint.CheckpointManager` (atomic, CRC-verified,
   schema/config-fingerprinted) so a killed service resumes
   bit-identically; the staging queue is never serialized — snapshots
   land at advance boundaries where it is empty, and the submission
   cursor replays the rest.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, checkpoint_meta
from repro.core.clustering import cluster_rounds_topk, visit_order
from repro.core.geometry import best_match_join, refine_join
from repro.core.segmentation import tsa1, tsa2
from repro.core.similarity import (build_subtraj_table_arrays,
                                   sort_topk_lists, topk_overflow)
from repro.core.types import DSCParams, SubtrajTable, TopKSim, TrajectoryBatch
from repro.core.voting import normalized_voting
from repro.core.windows import pack_bits
from repro.index.grid import TileBoxes, exact_pair_mask
from repro.stream.ingest import Ingestor, Records
from repro.stream.window import BackpressureOverflow, WindowManager

# bump when the snapshot layout changes; resume refuses mismatches
STREAM_SNAPSHOT_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Everything the streaming service needs, in one frozen record.

    Thresholds are **absolute** (``alpha_abs``/``k_abs`` >= 0 required):
    sigma-relative thresholds would drift with the window's similarity
    distribution, invalidating both the warm-start seeding and the
    advance-to-advance comparability of labels.
    """

    t_cap: int                    # window row capacity (objects)
    m_cap: int                    # per-row point capacity
    eps_sp: float
    eps_t: float
    alpha_abs: float
    k_abs: float
    allowed_lateness: float
    horizon: float
    max_subs: int = 4
    k: int = 8                    # neighbor-list width K (lists keep K+1)
    delta_t: float = 0.0
    w: int = 4
    tau: float = 0.4
    segmentation: str = "tsa1"
    queue_cap: int = 4096
    backpressure: str = "shed_oldest"   # "shed_oldest" | "block"
    on_dirty: str = "repair"            # "repair" | "drop" | "fail"
    max_speed: Optional[float] = None
    stall_advances: int = 0
    snapshot_every: int = 0             # 0 disables periodic snapshots
    warm_start: bool = True

    def validate(self) -> "StreamConfig":
        for name in ("t_cap", "m_cap", "max_subs", "k"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        if self.alpha_abs < 0 or self.k_abs < 0:
            raise ValueError(
                "streaming requires absolute thresholds: alpha_abs and "
                "k_abs must be >= 0 (sigma-relative thresholds drift with "
                "the window and break warm-start validity)")
        if self.horizon < self.allowed_lateness:
            raise ValueError(
                f"horizon ({self.horizon}) must cover allowed_lateness "
                f"({self.allowed_lateness}): a tolerably-late record must "
                "still land inside the active window")
        if self.segmentation not in ("tsa1", "tsa2"):
            raise ValueError(f"segmentation={self.segmentation!r}")
        if self.backpressure not in ("shed_oldest", "block"):
            raise ValueError(f"backpressure={self.backpressure!r}")
        if self.on_dirty not in ("repair", "drop", "fail"):
            raise ValueError(f"on_dirty={self.on_dirty!r}")
        return self

    @property
    def params(self) -> DSCParams:
        return DSCParams(
            eps_sp=self.eps_sp, eps_t=self.eps_t, delta_t=self.delta_t,
            w=self.w, tau=self.tau, alpha_abs=self.alpha_abs,
            k_abs=self.k_abs, max_subtrajs_per_traj=self.max_subs,
            segmentation=self.segmentation)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "StreamConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(f"unknown StreamConfig fields "
                             f"{sorted(unknown)}")
        return cls(**d).validate()

    def fingerprint(self) -> str:
        """Stable digest of the config; snapshots embed it and resume
        refuses state written under a different configuration."""
        return hashlib.sha1(
            json.dumps(self.to_dict(), sort_keys=True).encode()).hexdigest()


# --------------------------------------------------------------------------
# jitted pipeline pieces (module-level so retraces are bounded by the
# distinct padded dirty-row bucket sizes, not by driver instances)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("delta_t",))
def _delta_join(dx, dy, dt_, dvalid, dobj, wx, wy, wt, wvalid, wobj,
                fwd_mask, eps_sp, eps_t, *, delta_t: float):
    """Dirty-rows-vs-window join, both directions.

    Per-cell values equal the full batch join's: ``best_w[r, m, c]`` is a
    function of row ``r``'s and row ``c``'s points alone, and
    ``filter_delta_t`` acts per (ref row, cand col) independently.
    """
    ref = TrajectoryBatch(x=dx, y=dy, t=dt_, valid=dvalid, traj_id=dobj)
    cand = TrajectoryBatch(x=wx, y=wy, t=wt, valid=wvalid, traj_id=wobj)

    def run(r, c, mask):
        j = best_match_join(r, c, eps_sp, eps_t, prune_mask=mask)
        return refine_join(j, r.t, delta_t)

    fwd = run(ref, cand, fwd_mask)
    rev = run(cand, ref, fwd_mask.T)
    return fwd.best_w, fwd.best_idx, rev.best_w, rev.best_idx


@functools.partial(jax.jit, static_argnames=("segmentation", "w", "max_subs"))
def _window_tables(cube_w, wt, wvalid, tau, *, segmentation, w, max_subs):
    """Vote, segmentation and the ST relation from the standing cube —
    identical ops to ``run_dsc``'s segment stage over the same join."""
    vote = jnp.sum(cube_w, axis=-1)
    if segmentation == "tsa1":
        seg = tsa1(normalized_voting(vote, wvalid), wvalid, w, tau,
                   max_subs)
    else:
        seg = tsa2(pack_bits(cube_w > 0.0), wvalid, w, tau, max_subs)
    table = build_subtraj_table_arrays(wt, wvalid, seg.sub_local, vote,
                                       max_subs)
    return vote, seg.sub_local, table


@functools.partial(jax.jit, static_argnames=("max_subs", "kk"))
def _fresh_block(cube_w, cube_idx, sub_local, card, tvalid, dirty_rows, *,
                 max_subs, kk):
    """Exact similarity rows AND columns of the dirty slots.

    Scatter-adds the dirty rows' raw cube entries (forward: flat
    ``(d, m, c)`` order preserves the batch path's per-cell ``(m, c)``
    contribution subsequence) and the dirty columns (reverse: ``(r, m)``
    per fixed column), symmetrizes with max, then normalizes by
    ``min(card)`` — max-then-divide, which equals the batch path's
    divide-then-max because the denominator is symmetric in the pair and
    IEEE division by a positive value is monotone.

    Returns the dirty slots' own top-``kk`` lists (``fresh_*``) plus, for
    every slot of the window, the top-``min(kk, Sd)`` *candidates coming
    from dirty slots* (``cand_*``) — what clean rows fold into their
    purged standing lists.  Truncating candidates to ``kk`` is safe: a
    dirty-slot value dropped here is below ``kk`` dirty values already in
    the candidate list, so it can never enter a top-``kk``.
    """
    T, M = sub_local.shape
    S = T * max_subs
    Dp = dirty_rows.shape[0]
    Sd = Dp * max_subs
    ok = dirty_rows >= 0
    rsafe = jnp.clip(dirty_rows, 0, T - 1)

    w_rows = jnp.where(ok[:, None, None], cube_w[rsafe], 0.0)
    i_rows = cube_idx[rsafe]
    w_cols = jnp.where(ok[None, None, :], cube_w[:, :, rsafe], 0.0)
    i_cols = cube_idx[:, :, rsafe]
    dsub = sub_local[rsafe]

    # forward: raw rows of the dirty slots
    src_l = jnp.where(ok[:, None] & (dsub >= 0),
                      jnp.arange(Dp)[:, None] * max_subs + dsub, Sd)
    src_l = jnp.broadcast_to(src_l[:, :, None], (Dp, M, T))
    idx = jnp.clip(i_rows, 0, M - 1)
    cand_sub = sub_local[jnp.arange(T)[None, None, :], idx]
    dst_g = jnp.where((i_rows >= 0) & (cand_sub >= 0),
                      jnp.arange(T)[None, None, :] * max_subs + cand_sub, S)
    fwd = jnp.zeros((Sd + 1, S + 1), jnp.float32).at[
        src_l.reshape(-1), dst_g.reshape(-1)].add(w_rows.reshape(-1))

    # reverse: raw columns of the dirty slots
    src_g = jnp.where(sub_local >= 0,
                      jnp.arange(T)[:, None] * max_subs + sub_local, S)
    src_g = jnp.broadcast_to(src_g[:, :, None], (T, M, Dp))
    idxc = jnp.clip(i_cols, 0, M - 1)
    dsub_at = dsub[jnp.arange(Dp)[None, None, :], idxc]
    dst_l = jnp.where(ok[None, None, :] & (i_cols >= 0) & (dsub_at >= 0),
                      jnp.arange(Dp)[None, None, :] * max_subs + dsub_at, Sd)
    rev = jnp.zeros((Sd + 1, S + 1), jnp.float32).at[
        dst_l.reshape(-1), src_g.reshape(-1)].add(w_cols.reshape(-1))

    sym = jnp.maximum(fwd[:Sd, :S], rev[:Sd, :S])
    slot_ids = jnp.where(
        ok[:, None],
        rsafe[:, None] * max_subs + jnp.arange(max_subs)[None, :],
        -1).reshape(Sd).astype(jnp.int32)
    ssafe = jnp.clip(slot_ids, 0, S - 1)
    denom = jnp.minimum(card[ssafe][:, None], card[None, :])
    sim = sym / jnp.maximum(denom, 1).astype(jnp.float32)
    keep = ((slot_ids >= 0)[:, None] & tvalid[ssafe][:, None]
            & tvalid[None, :]
            & (slot_ids[:, None] != jnp.arange(S)[None, :]))
    sim = jnp.where(keep, sim, 0.0)

    vals, idxk = jax.lax.top_k(sim, kk)
    fresh_ids = jnp.where(vals > 0.0, idxk, -1).astype(jnp.int32)
    fresh_sims = jnp.maximum(vals, 0.0)

    kc = min(kk, Sd)
    cvals, cidx = jax.lax.top_k(sim.T, kc)
    cand_ids = jnp.where(cvals > 0.0,
                         slot_ids[cidx], -1).astype(jnp.int32)
    cand_sims = jnp.maximum(cvals, 0.0)
    return slot_ids, fresh_ids, fresh_sims, cand_ids, cand_sims


@jax.jit
def _merge_standing(standing_ids, standing_sims, slot_ids, fresh_ids,
                    fresh_sims, cand_ids, cand_sims, dirty_slot, tvalid):
    """Fold a fresh block into the standing ``[S, kk]`` lists.

    Dirty slots take their fresh lists outright.  Clean slots purge
    neighbors that are dirty or no longer valid, then merge the fresh
    column candidates via the canonical two-key sort (a set function, so
    the result is independent of how evidence arrived).  ``stale`` marks
    clean rows whose post-merge list cannot be proven complete (full
    before the purge, lost entries, and the new tail does not beat the
    old one) — the caller recomputes those outright in a second pass.
    """
    S, kk = standing_ids.shape
    tgt = jnp.where(slot_ids >= 0, slot_ids, S)
    f_ids = jnp.full((S + 1, kk), -1, jnp.int32).at[tgt].set(fresh_ids)[:S]
    f_sims = jnp.zeros((S + 1, kk), jnp.float32).at[tgt].set(
        fresh_sims)[:S]

    sid_safe = jnp.clip(standing_ids, 0, S - 1)
    purge = (standing_ids >= 0) & (dirty_slot[sid_safe]
                                   | ~tvalid[sid_safe])
    pos_before = jnp.sum(standing_ids >= 0, axis=1)
    full_before = pos_before == kk
    v_min = standing_sims[:, kk - 1]
    purged_ids = jnp.where(purge, -1, standing_ids)
    purged_sims = jnp.where(purge, 0.0, standing_sims)
    purged_any = jnp.any(purge, axis=1)

    m_ids, m_sims = sort_topk_lists(
        jnp.concatenate([purged_ids, cand_ids], axis=1),
        jnp.concatenate([purged_sims, cand_sims], axis=1), kk)
    m_ids = jnp.where(m_sims > 0.0, m_ids, -1)
    m_sims = jnp.maximum(m_sims, 0.0)

    new_ids = jnp.where(dirty_slot[:, None], f_ids, m_ids)
    new_sims = jnp.where(dirty_slot[:, None], f_sims, m_sims)
    new_ids = jnp.where(tvalid[:, None], new_ids, -1)
    new_sims = jnp.where(tvalid[:, None], new_sims, 0.0)

    stale = (~dirty_slot & tvalid & full_before & purged_any
             & (new_sims[:, kk - 1] <= v_min))
    changed = jnp.any((new_ids != standing_ids)
                      | (new_sims != standing_sims), axis=1)
    return new_ids, new_sims, stale, changed


@jax.jit
def _scatter_fresh(standing_ids, standing_sims, slot_ids, fresh_ids,
                   fresh_sims, tvalid):
    """Pass 2: overwrite the stale rows with their recomputed lists."""
    S, kk = standing_ids.shape
    tgt = jnp.where(slot_ids >= 0, slot_ids, S)
    new_ids = standing_ids.at[tgt].set(fresh_ids, mode="drop")
    new_sims = standing_sims.at[tgt].set(fresh_sims, mode="drop")
    new_ids = jnp.where(tvalid[:, None], new_ids, -1)
    new_sims = jnp.where(tvalid[:, None], new_sims, 0.0)
    changed = jnp.any((new_ids != standing_ids)
                      | (new_sims != standing_sims), axis=1)
    return new_ids, new_sims, changed


@functools.partial(jax.jit, static_argnames=("K",))
def _cluster_warm(ids, sims, t_start, t_end, voting, card, tvalid,
                  traj_row, params, prev_rank, prev_potential, prev_is_rep,
                  row_changed, has_prev, *, K):
    """Warm-started round-parallel Algorithm 4 over the standing lists.

    Seeds the visit-order prefix ``[0, r*)`` — every slot ranked before
    the first slot whose (rank, potential, list) changed — as resolved
    with its previous verdict.  Valid because a slot's verdict depends
    only on earlier-ranked slots' (rank, potential, list) inputs, all of
    which are unchanged inside the prefix.  The zeroed degree/moment
    fields are never read: StreamConfig enforces absolute thresholds, so
    ``resolve_thresholds`` ignores the moments entirely.
    """
    S, kk = ids.shape
    table = SubtrajTable(t_start=t_start, t_end=t_end, voting=voting,
                         card=card, valid=tvalid, traj_row=traj_row)
    spill = sims[:, K] if kk > K else jnp.zeros((S,), jnp.float32)
    zi = jnp.zeros((S,), jnp.int32)
    zf = jnp.zeros((S,), jnp.float32)
    topk = TopKSim(ids=ids[:, :K], sims=sims[:, :K], spill=spill,
                   degree=zi, row_sum=zf, row_sumsq=zf)

    order, rank = visit_order(table)
    potential = table.valid & (table.voting >= params.k_abs)
    flagged = ((rank != prev_rank) | (potential != prev_potential)
               | row_changed)
    r_star = jnp.min(jnp.where(flagged, rank, S))
    r_star = jnp.where(has_prev, r_star, 0)
    seed_resolved = rank < r_star
    seed_is_rep = prev_is_rep & seed_resolved

    result, rounds = cluster_rounds_topk(
        topk, table, params, with_rounds=True,
        seed_resolved=seed_resolved, seed_is_rep=seed_is_rep)
    overflow = topk_overflow(topk, result.alpha_used)
    return result, rounds, rank, potential, overflow, jnp.sum(seed_resolved)


def _pow2_bucket(n: int, cap: int) -> int:
    """Next power of two >= n, clamped to cap — bounds jit retraces to
    O(log cap) distinct dirty-row shapes."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class StreamDriver:
    """The incremental windowed-DSC service state machine (host side)."""

    def __init__(self, config: StreamConfig, *,
                 checkpoint_dir=None, telemetry=None, injector=None,
                 keep_n: int = 3):
        self.config = config.validate()
        c = self.config
        T, M, mS = c.t_cap, c.m_cap, c.max_subs
        S = T * mS
        self.S = S
        self.kk = min(c.k + 1, S)        # standing list width (K + spill)
        self.K = min(c.k, self.kk)

        self.telemetry = telemetry
        self.injector = injector
        self.ingest = Ingestor(on_dirty=c.on_dirty, max_speed=c.max_speed,
                               known_t_fn=self._known_t)
        self.window = WindowManager(
            allowed_lateness=c.allowed_lateness, horizon=c.horizon,
            queue_cap=c.queue_cap, policy=c.backpressure,
            stall_advances=c.stall_advances)
        self.manager = (CheckpointManager(checkpoint_dir, keep_n=keep_n)
                        if checkpoint_dir is not None else None)

        # ---- window store -------------------------------------------------
        self.obj_of_row = np.full((T,), -1, np.int64)
        self._row_of: dict[int, int] = {}
        self.xs = np.zeros((T, M), np.float32)
        self.ys = np.zeros((T, M), np.float32)
        self.ts = np.zeros((T, M), np.float32)
        self.valid = np.zeros((T, M), bool)
        # ---- standing derived state ---------------------------------------
        self.cube_w = np.zeros((T, M, T), np.float32)
        self.cube_idx = np.full((T, M, T), -1, np.int32)
        self.vote = np.zeros((T, M), np.float32)
        self.sub_local = np.full((T, M), -1, np.int32)
        self.bx_min = np.full((T,), np.inf, np.float32)
        self.bx_max = np.full((T,), -np.inf, np.float32)
        self.by_min = np.full((T,), np.inf, np.float32)
        self.by_max = np.full((T,), -np.inf, np.float32)
        self.bt_min = np.full((T,), np.inf, np.float32)
        self.bt_max = np.full((T,), -np.inf, np.float32)
        self.b_nonempty = np.zeros((T,), bool)
        self.standing_ids = np.full((S, self.kk), -1, np.int32)
        self.standing_sims = np.zeros((S, self.kk), np.float32)
        self.t_start = np.zeros((S,), np.float32)
        self.t_end = np.zeros((S,), np.float32)
        self.voting = np.zeros((S,), np.float32)
        self.card = np.zeros((S,), np.int32)
        self.tvalid = np.zeros((S,), bool)
        self.traj_row = np.repeat(np.arange(T, dtype=np.int32), mS)
        self.member_of = np.full((S,), -1, np.int32)
        self.member_sim = np.zeros((S,), np.float32)
        self.is_rep = np.zeros((S,), bool)
        self.is_outlier = np.zeros((S,), bool)
        self.alpha = float(c.alpha_abs)
        self.k_used = float(c.k_abs)
        self.prev_rank = np.zeros((S,), np.int32)
        self.prev_potential = np.zeros((S,), bool)
        self.prev_is_rep = np.zeros((S,), bool)
        self.has_prev = False
        # ---- counters ------------------------------------------------------
        self.advance_count = 0
        self.cursor = 0                  # next submission-batch index
        self.evicted_points = 0
        self.shed_capacity = 0           # records shed for lack of a row
        self.row_overflow = 0            # oldest points dropped from a row
        self.overflow_events = 0         # advances with topk overflow > 0
        self.inserted_total = 0
        self.last_rounds = 0
        self.warm_prefix = 0

    # ------------------------------------------------------------- plumbing
    def _known_t(self, obj: int) -> np.ndarray:
        r = self._row_of.get(int(obj))
        if r is None:
            return np.empty((0,), np.float32)
        return self.ts[r][self.valid[r]]

    def _emit(self, event: str, **fields):
        if self.telemetry is not None:
            self.telemetry.emit(event, **fields)

    # ------------------------------------------------------------------ api
    def submit(self, recs: Records) -> int:
        """Validate and stage one submission batch; returns its absolute
        index (the fault plan's and the resume cursor's key)."""
        idx = self.cursor
        self.cursor += 1
        if self.injector is not None:
            recs = self.injector.on_stream_batch(idx, recs)
        before = dict(self.ingest.counters)
        admitted = self.ingest.process(recs)      # may raise PoisonRecord
        deltas = {r: self.ingest.counters[r] - before[r]
                  for r in before if self.ingest.counters[r] > before[r]}
        if deltas:
            self._emit("record_quarantined", batch=idx,
                       total=int(sum(deltas.values())), **deltas)
        shed = self.window.stage(admitted)  # may raise BackpressureOverflow
        if shed:
            self._emit("backpressure", batch=idx, kind="queue_shed",
                       shed=int(shed))
        return idx

    def _insert(self, recs: Records, dirty: set) -> None:
        c = self.config
        T, M = c.t_cap, c.m_cap
        for i in range(recs.n):
            obj = int(recs.obj[i])
            r = self._row_of.get(obj)
            if r is None:
                free = np.nonzero(self.obj_of_row < 0)[0]
                if free.size == 0:
                    if self.window.policy == "block":
                        raise BackpressureOverflow(
                            f"window store full ({T} rows) and object "
                            f"{obj} needs a new row")
                    self.shed_capacity += 1
                    self._emit("backpressure", kind="capacity", obj=obj)
                    continue
                r = int(free[0])
                self.obj_of_row[r] = obj
                self._row_of[obj] = r
            n = int(np.sum(self.valid[r]))
            if n >= M:
                # drop the row's oldest point to admit the new one
                self.xs[r, :M - 1] = self.xs[r, 1:]
                self.ys[r, :M - 1] = self.ys[r, 1:]
                self.ts[r, :M - 1] = self.ts[r, 1:]
                n = M - 1
                self.valid[r, :] = False
                self.valid[r, :n] = True
                self.row_overflow += 1
            pos = int(np.searchsorted(self.ts[r, :n],
                                      np.float32(recs.t[i]), side="right"))
            # np.insert allocates a fresh row — safe for the overlapping
            # shift an in-place slice assignment would corrupt
            self.xs[r, :n + 1] = np.insert(self.xs[r, :n], pos, recs.x[i])
            self.ys[r, :n + 1] = np.insert(self.ys[r, :n], pos, recs.y[i])
            self.ts[r, :n + 1] = np.insert(self.ts[r, :n], pos, recs.t[i])
            self.valid[r, n] = True
            self.inserted_total += 1
            dirty.add(r)

    def _evict(self, dirty: set) -> int:
        cutoff = self.window.evict_before()
        if not np.isfinite(cutoff):
            return 0
        evicted = 0
        for r in range(self.config.t_cap):
            if self.obj_of_row[r] < 0:
                continue
            n = int(np.sum(self.valid[r]))
            keep = self.ts[r, :n] >= np.float32(cutoff)
            kn = int(np.sum(keep))
            if kn == n:
                continue
            evicted += n - kn
            self.xs[r, :kn] = self.xs[r, :n][keep]
            self.ys[r, :kn] = self.ys[r, :n][keep]
            self.ts[r, :kn] = self.ts[r, :n][keep]
            self.xs[r, kn:] = 0.0
            self.ys[r, kn:] = 0.0
            self.ts[r, kn:] = 0.0
            self.valid[r, :] = False
            self.valid[r, :kn] = True
            dirty.add(r)
            if kn == 0:
                del self._row_of[int(self.obj_of_row[r])]
                self.obj_of_row[r] = -1
        self.evicted_points += evicted
        return evicted

    def _update_bboxes(self, rows) -> None:
        for r in rows:
            v = self.valid[r]
            if not v.any():
                self.bx_min[r] = self.by_min[r] = self.bt_min[r] = np.inf
                self.bx_max[r] = self.by_max[r] = self.bt_max[r] = -np.inf
                self.b_nonempty[r] = False
                continue
            self.bx_min[r] = self.xs[r][v].min()
            self.bx_max[r] = self.xs[r][v].max()
            self.by_min[r] = self.ys[r][v].min()
            self.by_max[r] = self.ys[r][v].max()
            self.bt_min[r] = self.ts[r][v].min()
            self.bt_max[r] = self.ts[r][v].max()
            self.b_nonempty[r] = True

    def _padded_rows(self, rows: np.ndarray) -> np.ndarray:
        Dp = _pow2_bucket(max(int(rows.size), 1), self.config.t_cap)
        out = np.full((Dp,), -1, np.int64)
        out[:rows.size] = rows
        return out

    def _delta_arrays(self, rows: np.ndarray):
        """Gather padded dirty-row slices of the store (padding rows are
        all-invalid with obj -1, so they join to nothing)."""
        M = self.config.m_cap
        Dp = rows.shape[0]
        dx = np.zeros((Dp, M), np.float32)
        dy = np.zeros((Dp, M), np.float32)
        dt = np.zeros((Dp, M), np.float32)
        dv = np.zeros((Dp, M), bool)
        dobj = np.full((Dp,), -1, np.int32)
        ok = rows >= 0
        sel = rows[ok]
        dx[ok] = self.xs[sel]
        dy[ok] = self.ys[sel]
        dt[ok] = self.ts[sel]
        dv[ok] = self.valid[sel]
        dobj[ok] = self.obj_of_row[sel].astype(np.int32)
        return dx, dy, dt, dv, dobj

    def _boxes(self, rows: np.ndarray = None) -> TileBoxes:
        if rows is None:
            return TileBoxes(
                xmin=jnp.asarray(self.bx_min), xmax=jnp.asarray(self.bx_max),
                ymin=jnp.asarray(self.by_min), ymax=jnp.asarray(self.by_max),
                tmin=jnp.asarray(self.bt_min), tmax=jnp.asarray(self.bt_max),
                nonempty=jnp.asarray(self.b_nonempty))
        ok = rows >= 0
        sel = np.clip(rows, 0, self.config.t_cap - 1)

        def g(a, fill):
            out = a[sel].copy()
            out[~ok] = fill
            return jnp.asarray(out)

        return TileBoxes(
            xmin=g(self.bx_min, np.inf), xmax=g(self.bx_max, -np.inf),
            ymin=g(self.by_min, np.inf), ymax=g(self.by_max, -np.inf),
            tmin=g(self.bt_min, np.inf), tmax=g(self.bt_max, -np.inf),
            nonempty=jnp.asarray(np.where(ok, self.b_nonempty[sel], False)))

    # ------------------------------------------------------------- advance
    def advance(self) -> dict:
        """Drain the staging queue and bring every piece of standing
        state up to date with the new window contents."""
        c = self.config
        if self.injector is not None:
            self.injector.on_window_advance(self.advance_count)
        admitted, n_late = self.window.drain()    # may raise WatermarkStall
        if n_late:
            self._emit("late_dropped", advance=self.advance_count,
                       dropped=int(n_late),
                       watermark=float(self.window.watermark))

        dirty: set = set()
        inserted_before = self.inserted_total
        self._insert(admitted, dirty)
        inserted = self.inserted_total - inserted_before
        evicted = self._evict(dirty)

        if not dirty:
            self.advance_count += 1
            self._emit("window_advanced", advance=self.advance_count - 1,
                       watermark=float(self.window.watermark),
                       admitted=int(admitted.n), late=int(n_late),
                       inserted=0, evicted=0, dirty_rows=0, sim_rows=0,
                       pass2_rows=0, rounds=int(self.last_rounds),
                       warm_prefix=int(self.warm_prefix), noop=True,
                       reps=int(np.sum(self.is_rep)),
                       outliers=int(np.sum(self.is_outlier)), overflow=0)
            self._maybe_snapshot()
            return {"advance": self.advance_count - 1, "dirty_rows": 0,
                    "noop": True}

        D = np.asarray(sorted(dirty), np.int64)
        self._update_bboxes(D)

        # --- delta join ----------------------------------------------------
        rows = self._padded_rows(D)
        dx, dy, dt, dv, dobj = self._delta_arrays(rows)
        fwd_mask = exact_pair_mask(self._boxes(rows), self._boxes(),
                                   np.float32(c.eps_sp),
                                   np.float32(c.eps_t))
        fw, fi, rw, ri = _delta_join(
            dx, dy, dt, dv, dobj,
            jnp.asarray(self.xs), jnp.asarray(self.ys),
            jnp.asarray(self.ts), jnp.asarray(self.valid),
            jnp.asarray(self.obj_of_row.astype(np.int32)),
            fwd_mask, np.float32(c.eps_sp), np.float32(c.eps_t),
            delta_t=float(c.delta_t))
        fw, fi = np.asarray(fw), np.asarray(fi)
        rw, ri = np.asarray(rw), np.asarray(ri)
        nD = D.size
        self.cube_w[D] = fw[:nD]
        self.cube_idx[D] = fi[:nD]
        self.cube_w[:, :, D] = rw[:, :, :nD]
        self.cube_idx[:, :, D] = ri[:, :, :nD]

        # --- vote / segmentation / ST --------------------------------------
        old_sub = self.sub_local.copy()
        vote, sub_local, table = _window_tables(
            jnp.asarray(self.cube_w), jnp.asarray(self.ts),
            jnp.asarray(self.valid), np.float32(c.tau),
            segmentation=c.segmentation, w=c.w, max_subs=c.max_subs)
        self.vote = np.asarray(vote)
        self.sub_local = np.asarray(sub_local)
        self.t_start = np.asarray(table.t_start)
        self.t_end = np.asarray(table.t_end)
        self.voting = np.asarray(table.voting)
        self.card = np.asarray(table.card)
        self.tvalid = np.asarray(table.valid)

        struct_dirty = np.nonzero(
            np.any(self.sub_local != old_sub, axis=1))[0]
        D_sim = np.union1d(D, struct_dirty).astype(np.int64)

        # --- similarity: fresh block + standing merge ------------------------
        rows_sim = self._padded_rows(D_sim)
        dirty_slot = np.zeros((self.S,), bool)
        for r in D_sim:
            dirty_slot[int(r) * c.max_subs:(int(r) + 1) * c.max_subs] = True

        slot_ids, f_ids, f_sims, cd_ids, cd_sims = _fresh_block(
            jnp.asarray(self.cube_w), jnp.asarray(self.cube_idx),
            jnp.asarray(self.sub_local), jnp.asarray(self.card),
            jnp.asarray(self.tvalid), jnp.asarray(rows_sim),
            max_subs=c.max_subs, kk=self.kk)
        new_ids, new_sims, stale, changed = _merge_standing(
            jnp.asarray(self.standing_ids),
            jnp.asarray(self.standing_sims),
            slot_ids, f_ids, f_sims, cd_ids, cd_sims,
            jnp.asarray(dirty_slot), jnp.asarray(self.tvalid))
        stale = np.asarray(stale)
        changed = np.asarray(changed)

        pass2_rows = 0
        if stale.any():
            # recompute stale rows outright; pass 2 purges nothing, so it
            # cannot create new staleness — two passes always suffice
            rows2 = np.unique(np.nonzero(stale)[0] // c.max_subs)
            pass2_rows = int(rows2.size)
            rows2p = self._padded_rows(rows2.astype(np.int64))
            slot2, f2_ids, f2_sims, _, _ = _fresh_block(
                jnp.asarray(self.cube_w), jnp.asarray(self.cube_idx),
                jnp.asarray(self.sub_local), jnp.asarray(self.card),
                jnp.asarray(self.tvalid), jnp.asarray(rows2p),
                max_subs=c.max_subs, kk=self.kk)
            new_ids, new_sims, changed2 = _scatter_fresh(
                new_ids, new_sims, slot2, f2_ids, f2_sims,
                jnp.asarray(self.tvalid))
            changed = changed | np.asarray(changed2)

        self.standing_ids = np.asarray(new_ids)
        self.standing_sims = np.asarray(new_sims)

        # --- clustering (warm-started) ----------------------------------------
        result, rounds, rank, potential, overflow, warm_n = _cluster_warm(
            jnp.asarray(self.standing_ids),
            jnp.asarray(self.standing_sims),
            jnp.asarray(self.t_start), jnp.asarray(self.t_end),
            jnp.asarray(self.voting), jnp.asarray(self.card),
            jnp.asarray(self.tvalid), jnp.asarray(self.traj_row),
            c.params, jnp.asarray(self.prev_rank),
            jnp.asarray(self.prev_potential),
            jnp.asarray(self.prev_is_rep), jnp.asarray(changed),
            np.bool_(self.has_prev and c.warm_start), K=self.K)
        self.member_of = np.asarray(result.member_of)
        self.member_sim = np.asarray(result.member_sim)
        self.is_rep = np.asarray(result.is_rep)
        self.is_outlier = np.asarray(result.is_outlier)
        self.alpha = float(result.alpha_used)
        self.k_used = float(result.k_used)
        self.last_rounds = int(rounds)
        self.warm_prefix = int(warm_n)
        self.prev_rank = np.asarray(rank)
        self.prev_potential = np.asarray(potential)
        self.prev_is_rep = self.is_rep.copy()
        self.has_prev = True
        n_over = int(np.sum(np.asarray(overflow) > 0))
        if n_over:
            self.overflow_events += 1

        summary = {
            "advance": self.advance_count,
            "watermark": float(self.window.watermark),
            "admitted": int(admitted.n), "late": int(n_late),
            "inserted": int(inserted), "evicted": int(evicted),
            "dirty_rows": int(D.size), "sim_rows": int(D_sim.size),
            "pass2_rows": pass2_rows, "rounds": int(rounds),
            "warm_prefix": int(warm_n),
            "reps": int(np.sum(self.is_rep)),
            "outliers": int(np.sum(self.is_outlier)),
            "overflow": n_over,
        }
        self._emit("window_advanced", **summary)
        self.advance_count += 1
        self._maybe_snapshot()
        return summary

    # ------------------------------------------------------------ snapshots
    def _maybe_snapshot(self):
        if (self.manager is not None and self.config.snapshot_every
                and self.advance_count % self.config.snapshot_every == 0):
            self.snapshot()

    def snapshot(self):
        """Full-state snapshot at an advance boundary (queue must be
        empty — the submission cursor replays anything staged later)."""
        if self.window.queued() > 0:
            raise RuntimeError(
                "snapshot with a non-empty staging queue would lose "
                f"{self.window.queued()} records: advance() first")
        if self.manager is None:
            raise RuntimeError("no checkpoint_dir configured")
        tree = {
            "store": {"obj": self.obj_of_row, "x": self.xs, "y": self.ys,
                      "t": self.ts, "valid": self.valid},
            "cube": {"w": self.cube_w, "idx": self.cube_idx},
            "seg": {"sub_local": self.sub_local},
            "vote": {"vote": self.vote},
            "bbox": {"xmin": self.bx_min, "xmax": self.bx_max,
                     "ymin": self.by_min, "ymax": self.by_max,
                     "tmin": self.bt_min, "tmax": self.bt_max,
                     "nonempty": self.b_nonempty},
            "standing": {"ids": self.standing_ids,
                         "sims": self.standing_sims},
            "table": {"t_start": self.t_start, "t_end": self.t_end,
                      "voting": self.voting, "card": self.card,
                      "valid": self.tvalid},
            "labels": {"member_of": self.member_of,
                       "member_sim": self.member_sim,
                       "is_rep": self.is_rep,
                       "is_outlier": self.is_outlier,
                       "thresholds": np.asarray(
                           [self.alpha, self.k_used], np.float32)},
            "warm": {"prev_rank": self.prev_rank,
                     "prev_potential": self.prev_potential,
                     "prev_is_rep": self.prev_is_rep,
                     "has_prev": np.asarray([self.has_prev])},
            "driver": {"scalars": np.asarray(
                [self.advance_count, self.cursor, self.evicted_points,
                 self.shed_capacity, self.row_overflow,
                 self.overflow_events, self.inserted_total], np.int64)},
            "ingest": self.ingest.state_arrays(),
            "window": self.window.state_arrays(),
        }
        self.manager.save(self.advance_count, tree, meta={
            "schema": STREAM_SNAPSHOT_SCHEMA,
            "fingerprint": self.config.fingerprint()})

    def maybe_resume(self) -> bool:
        """Restore the newest valid snapshot, falling back step by step
        past corrupt ones.  Returns True when state was restored."""
        if self.manager is None:
            return False
        steps = self.manager.available_steps()
        if not steps:
            return False
        for step in reversed(steps):
            meta = checkpoint_meta(self.manager.root, step)
            if not meta or meta.get("schema") != STREAM_SNAPSHOT_SCHEMA \
                    or meta.get("fingerprint") != self.config.fingerprint():
                raise ValueError(
                    f"snapshot step {step} was written under a different "
                    "schema/config — refusing to resume into it")
            try:
                flat, _ = self.manager.restore_flat(step)
            except IOError:
                continue             # corrupt leaves: fall back a step
            self._load(flat)
            return True
        return False

    def _load(self, flat: dict):
        self.obj_of_row = flat["store/obj"].astype(np.int64)
        self.xs = flat["store/x"]
        self.ys = flat["store/y"]
        self.ts = flat["store/t"]
        self.valid = flat["store/valid"].astype(bool)
        self._row_of = {int(o): r for r, o in enumerate(self.obj_of_row)
                        if o >= 0}
        self.cube_w = flat["cube/w"]
        self.cube_idx = flat["cube/idx"]
        self.sub_local = flat["seg/sub_local"]
        self.vote = flat["vote/vote"]
        self.bx_min = flat["bbox/xmin"]
        self.bx_max = flat["bbox/xmax"]
        self.by_min = flat["bbox/ymin"]
        self.by_max = flat["bbox/ymax"]
        self.bt_min = flat["bbox/tmin"]
        self.bt_max = flat["bbox/tmax"]
        self.b_nonempty = flat["bbox/nonempty"].astype(bool)
        self.standing_ids = flat["standing/ids"]
        self.standing_sims = flat["standing/sims"]
        self.t_start = flat["table/t_start"]
        self.t_end = flat["table/t_end"]
        self.voting = flat["table/voting"]
        self.card = flat["table/card"]
        self.tvalid = flat["table/valid"].astype(bool)
        self.member_of = flat["labels/member_of"]
        self.member_sim = flat["labels/member_sim"]
        self.is_rep = flat["labels/is_rep"].astype(bool)
        self.is_outlier = flat["labels/is_outlier"].astype(bool)
        self.alpha = float(flat["labels/thresholds"][0])
        self.k_used = float(flat["labels/thresholds"][1])
        self.prev_rank = flat["warm/prev_rank"]
        self.prev_potential = flat["warm/prev_potential"].astype(bool)
        self.prev_is_rep = flat["warm/prev_is_rep"].astype(bool)
        self.has_prev = bool(flat["warm/has_prev"][0])
        (self.advance_count, self.cursor, self.evicted_points,
         self.shed_capacity, self.row_overflow, self.overflow_events,
         self.inserted_total) = (int(v) for v in flat["driver/scalars"])
        self.ingest.load_state_arrays(
            {k.split("/", 1)[1]: v for k, v in flat.items()
             if k.startswith("ingest/")})
        self.window.load_state_arrays(
            {k.split("/", 1)[1]: v for k, v in flat.items()
             if k.startswith("window/")})

    # -------------------------------------------------------------- queries
    def query(self, obj: int) -> dict:
        """Current subtrajectories + cluster assignment of one object."""
        c = self.config
        out = {"obj": int(obj), "in_window": False,
               "watermark": float(self.window.watermark), "subtrajs": []}
        r = self._row_of.get(int(obj))
        if r is None:
            return out
        out["in_window"] = True
        for s in range(c.max_subs):
            slot = r * c.max_subs + s
            if not self.tvalid[slot]:
                continue
            entry = {"sub": s, "slot": int(slot),
                     "t_start": float(self.t_start[slot]),
                     "t_end": float(self.t_end[slot]),
                     "is_rep": bool(self.is_rep[slot]),
                     "is_outlier": bool(self.is_outlier[slot]),
                     "cluster": None}
            rep = int(self.member_of[slot])
            if rep >= 0:
                entry["cluster"] = {
                    "rep_obj": int(self.obj_of_row[rep // c.max_subs]),
                    "rep_sub": rep % c.max_subs, "rep_slot": rep,
                    "sim": float(self.member_sim[slot])}
            out["subtrajs"].append(entry)
        return out

    def stats(self) -> dict:
        return {
            "advances": self.advance_count,
            "cursor": self.cursor,
            "watermark": float(self.window.watermark),
            "objects": len(self._row_of),
            "points": int(np.sum(self.valid)),
            "submitted": self.ingest.submitted,
            "admitted": self.ingest.admitted,
            "quarantined": dict(self.ingest.counters),
            "repaired_order": self.ingest.repaired_order,
            "late_dropped": self.window.late_dropped,
            "shed_queue": self.window.shed,
            "shed_capacity": self.shed_capacity,
            "row_overflow": self.row_overflow,
            "inserted": self.inserted_total,
            "evicted": self.evicted_points,
            "reps": int(np.sum(self.is_rep)),
            "outliers": int(np.sum(self.is_outlier)),
            "overflow_events": self.overflow_events,
            "last_rounds": self.last_rounds,
            "warm_prefix": self.warm_prefix,
        }

    def accounting(self) -> dict:
        """The no-silent-drops invariant: every submitted record is
        admitted into the store, quarantined, dropped late, shed, or
        still staged — and the books must balance exactly."""
        lhs = self.ingest.submitted
        rhs = (self.ingest.quarantined_total() + self.window.late_dropped
               + self.window.shed + self.shed_capacity
               + self.inserted_total + self.window.queued())
        return {"submitted": int(lhs),
                "quarantined": int(self.ingest.quarantined_total()),
                "late_dropped": int(self.window.late_dropped),
                "shed_queue": int(self.window.shed),
                "shed_capacity": int(self.shed_capacity),
                "inserted": int(self.inserted_total),
                "queued": int(self.window.queued()),
                "balanced": bool(lhs == rhs)}

    def window_batch(self) -> TrajectoryBatch:
        """The active window as a batch — the oracle cross-check feeds
        this straight into ``run_dsc``."""
        return TrajectoryBatch(
            x=jnp.asarray(self.xs), y=jnp.asarray(self.ys),
            t=jnp.asarray(self.ts), valid=jnp.asarray(self.valid),
            traj_id=jnp.asarray(self.obj_of_row.astype(np.int32)))
