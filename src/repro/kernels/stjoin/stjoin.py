"""Pallas TPU kernels: best-match spatiotemporal join (DTJ's Join step).

Contract
--------
Given ``P`` reference points (flattened, with per-point trajectory ids) and
``C`` candidate trajectories of up to ``Mc`` points each, compute for every
(ref point p, candidate trajectory c):

    best_w[p, c]   = max over candidate points m of
                     (1 - d_sp(p, (c,m)) / eps_sp)
                     subject to d_sp <= eps_sp, |dt| <= eps_t,
                     validity, and traj_id[p] != cand_id[c]
    best_idx[p, c] = argmax m (first on ties), or -1 when nothing matches

Layout
------
Every kernel works on a ``[bp, bc]`` tile with reference points on
sublanes and candidate trajectories on lanes:

* reference operands are ``[P, 1]`` columns (block ``(bp, 1)``), broadcast
  along lanes;
* candidate operands are transposed to ``[Mc, C]`` (block ``(., bc)``), and
  the sweep walks the candidate-point axis one row at a time: a dynamic
  sublane-row load broadcast along sublanes.  The max/argmax contraction
  is then an elementwise running carry on the VPU with a strict ``>``
  update, which keeps the first maximum exactly like ``jnp.argmax``;
* scalars (eps, the tile plan, reference sub-slots) live in SMEM.

Mosaic therefore needs ``bc`` to be a multiple of 128 or the whole padded
candidate axis, and ``bp`` a multiple of 8; interpret mode takes any tile.
The distance is a broadcast subtract on the VPU: the contraction depth is
2 (x, y), far too shallow for the MXU, so the join is VPU-bound and the
point of the fused epilogues is to avoid writing the ``[P, C]`` cube.

Tile plans
----------
Every kernel runs over a (ref block i, slot s) grid.  Slot ``s`` names a
candidate block through a scalar-prefetched code table
(:func:`slot_codes`): the dense sweep is the plan that lists every block,
and the index-pruned sweep (``repro.index.grid``) lists only the blocks
that can hold a match.  Pruning is conservative, so both give the same
result; a dead slot computes nothing.

Kernels
-------
* ``stjoin_pallas``          the materializing join: ``(best_w, best_idx)
                             [P, C]``; the grid's last axis walks ``bm``
                             candidate-point chunks.
* ``stjoin_dtj_pallas``      the same join with the delta_t run refine
                             applied in-kernel to each tile's best matches,
                             and the matches counted before and after it.
* ``stjoin_vote_fused_flat`` fused pass 1: per-point vote sums (Eq. 4) and
                             bit-packed neighbor words (TSA2, Alg. 3) with
                             the delta_t run refine applied in-kernel.
* ``stjoin_sim_fused_flat``  fused pass 2: the raw similarity scatter
                             (Eq. 2), one ``[rows*ms, bc*ms]`` block per
                             tile (a tile's matches can only land in the
                             slots of its own rows and candidates).
* ``stjoin_sim_panel_flat``  fused pass 2 restricted to one row panel, in
                             both orientations (top-K engine, DESIGN §8).
* ``stjoin_sim_fused_flat_cube``
                             pass 2 from a materialized join: the same raw
                             blocks, read from the join's ``best_w`` and
                             ``best_idx`` tiles instead of recomputed
                             (materialize mode, dense similarity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret, shift_points

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_ANY = pl.BlockSpec(memory_space=pl.ANY)
_BIG = 3.4e38


# ---------------------------------------------------------------------------
# Tile plans
# ---------------------------------------------------------------------------


def slot_codes(tile_ids):
    """``[nRb, K]`` candidate block ids (-1 padded, ascending) -> codes.

    ``code >= 0``: a live slot that sweeps candidate block ``code``.
    ``code < 0``: a dead slot that computes nothing; ``-code - 1`` is the
    block its index maps name.  That is the row's last live block, so the
    pipeline sees a revisit and writes the block back unchanged, or block
    0 of a row with no survivor, which slot 0 fills with the empty value.
    """
    live = tile_ids >= 0
    last = jax.lax.cummax(jnp.where(live, tile_ids, -1), axis=1)
    return jnp.where(live, tile_ids, -jnp.maximum(last, 0) - 1).astype(
        jnp.int32)


def dense_codes(n_ref_blocks: int, n_cand_blocks: int, first: int = 0):
    """The plan that sweeps candidate blocks ``first ..`` for every row."""
    return jnp.broadcast_to(
        first + jnp.arange(n_cand_blocks, dtype=jnp.int32),
        (n_ref_blocks, n_cand_blocks))


def _block_of(code):
    return jnp.where(code >= 0, code, -code - 1)


def _eps(*vals):
    return jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])


def _col(a, dtype):
    return a.astype(dtype).reshape(-1, 1)


# ---------------------------------------------------------------------------
# Tile bodies shared by every kernel
# ---------------------------------------------------------------------------


def _sweep(ref, cand, cid, eps_sp, eps_t, carry, *, n, base=0, csub=None):
    """Running best match of the tile over candidate-point rows ``[0, n)``.

    ``ref``: ``(x, y, t, id, ok)`` ``[bp, 1]`` values; ``cand``: ``(x, y,
    t, ok)`` refs of shape ``[n, bc]``; ``cid``: ``[1, bc]``.  ``carry``
    is ``(w,)`` or ``(w, follow)``; only a strictly larger positive weight
    replaces the running best, so ties keep the lowest point index and a
    pair with no match keeps its initial value.  ``follow`` moves with the
    best match: the point index ``base + m``, or, given ``csub`` (an
    ``[n, bc]`` ref), the candidate point's sub-slot, which spares pass 2
    a gather.
    """
    rx, ry, rt, rid, rok = ref
    cx, cy, ct, cok = cand
    eps2 = eps_sp * eps_sp
    pair_ok = (rok != 0) & (rid != cid)                     # [bp, bc]

    def body(m, c):
        row = pl.ds(m, 1)
        dx = rx - cx[row, :]
        dy = ry - cy[row, :]
        d2 = dx * dx + dy * dy
        ok = (pair_ok & (cok[row, :] != 0) & (d2 <= eps2)
              & (jnp.abs(rt - ct[row, :]) <= eps_t))
        w = 1.0 - jnp.sqrt(d2) / eps_sp
        better = ok & (w > c[0])
        follow = base + m if csub is None else csub[row, :]
        return (jnp.where(better, w, c[0]),) + tuple(
            jnp.where(better, follow, x) for x in c[1:])

    return jax.lax.fori_loop(0, n, body, carry)


def _refs(*refs):
    return tuple(r[...] for r in refs)


def _run_keep(w, rt, rows: int, M: int, delta_t):
    """In-kernel DTJ Refine (delta_t): which matches survive.

    ``w``: ``[bp, bc]`` best weights for ``rows`` whole trajectory rows of
    ``M`` points (``bp == rows * M``; the wrappers keep ref blocks
    row-aligned so runs never cross a block).  A run is a maximal streak
    of consecutive matched ref points for one candidate; it survives iff
    ``t[last] - t[first] >= delta_t``.  With ``t`` ascending in a row, a
    point's run starts at the latest run start at or before it and ends
    at the earliest run end at or after it: a prefix max and a suffix min,
    done as log-step doubling scans (exact for max and min).  Matches
    ``repro.core.geometry.filter_delta_t``; ``delta_t == 0`` keeps every
    match.  Returns ``keep [bp, bc]`` bool.
    """
    bp, bc = w.shape
    matched = (w.reshape(rows, M, bc) > 0.0).astype(jnp.int32)
    prev = shift_points(matched, 1, 0)
    nxt = shift_points(matched, -1, 0)
    t3 = jnp.broadcast_to(rt.reshape(rows, M, 1), (rows, M, bc))
    start = jnp.where((matched > prev), t3, -_BIG)
    end = jnp.where((matched > nxt), t3, _BIG)
    k = 1
    while k < M:
        start = jnp.maximum(start, shift_points(start, k, -_BIG))
        end = jnp.minimum(end, shift_points(end, -k, _BIG))
        k *= 2
    keep = (matched > 0) & ((end - start) >= delta_t)
    return keep.reshape(bp, bc)


def _run_refine(w, rt, rows: int, M: int, delta_t):
    """``w`` with the matches ``_run_keep`` drops zeroed."""
    return jnp.where(_run_keep(w, rt, rows, M, delta_t), w, 0.0)


def _pack_words(w, j, out_words):
    """OR the tile's match bits into the packed-word accumulator.

    Candidate ``g = j * bc + lane`` is bit ``g % 32`` of word ``g // 32``;
    a block touches ``max(1, bc // 32)`` words (``bc`` divides 32 or is a
    multiple of it).  Bits are disjoint, so an int32 lane sum is an OR.
    """
    bc = w.shape[1]
    g = j * bc + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    bits = jnp.where(w > 0.0, jnp.left_shift(1, g & 31), 0)
    lo = (j * bc) // 32
    word = jax.lax.broadcasted_iota(jnp.int32, (1, out_words.shape[1]), 1)
    acc = out_words[...]
    for q in range(max(1, bc // 32)):
        val = jnp.sum(jnp.where((g >> 5) == lo + q, bits, 0), axis=1,
                      keepdims=True)
        acc = acc + jnp.where(word == lo + q, val, 0)
    out_words[...] = acc


# ---------------------------------------------------------------------------
# Materializing join
# ---------------------------------------------------------------------------


def _join_kernel(codes, rx, ry, rt, rid, rok, cx, cy, ct, cid, cok, eps,
                 w0, i0, out_w, out_idx, *out_cnt, rows: int = 0, M: int = 0):
    """``out_cnt`` is there only in the DTJ join (``stjoin_dtj_pallas``):
    the last point chunk of a live tile then applies the run refine to the
    tile's best matches before they are written, and adds the tile's
    matches before and after it to lanes 0 and 1 of the row block's
    counts."""
    del w0, i0                    # aliased empty outputs (unvisited blocks)
    i, s, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = codes[i, s] >= 0

    @pl.when((k == 0) & (live | (s == 0)))
    def _init():
        out_w[...] = jnp.zeros_like(out_w)
        out_idx[...] = jnp.full_like(out_idx, -1)

    for cnt in out_cnt:
        @pl.when((k == 0) & (s == 0))
        def _init_cnt():
            cnt[...] = jnp.zeros_like(cnt)

    @pl.when(live)
    def _chunk():
        bm = cx.shape[0]
        w, idx = _sweep(_refs(rx, ry, rt, rid, rok), (cx, cy, ct, cok),
                        cid[...], eps[0], eps[1], (out_w[...], out_idx[...]),
                        n=bm, base=k * bm)
        out_w[...] = w
        out_idx[...] = idx
        for cnt in out_cnt:
            @pl.when(k == pl.num_programs(2) - 1)
            def _refine():
                keep = _run_keep(w, rt[...], rows, M, eps[2])
                out_w[...] = jnp.where(keep, w, 0.0)
                out_idx[...] = jnp.where(keep, idx, -1)

                def total(x):
                    x = jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True)
                    return jnp.sum(x, axis=0, keepdims=True)   # [1, 1]

                lane = jax.lax.broadcasted_iota(jnp.int32, cnt.shape, 2)
                cnt[...] += jnp.where(
                    lane == 0, total(w > 0.0)[None],
                    jnp.where(lane == 1, total(keep)[None], 0))


def _join_call(ref_ops, cand_ops, eps, tile_ids, *, bp: int, bc: int,
               bm: int, interpret: bool, rows: int = 0, M: int = 0):
    """The materializing join's ``pallas_call``, ``eps`` its scalars; with
    ``rows`` (the DTJ join, ``bp == rows * M``, ``eps`` with ``delta_t``)
    it refines in-kernel and also returns the ``[P/bp, 1, 128]`` per-block
    match counts."""
    if interpret is None:
        interpret = default_interpret()
    P = ref_ops[0].shape[0]
    C, Mc = cand_ops[0].shape
    assert P % bp == 0 and C % bc == 0 and Mc % bm == 0, (P, C, Mc, bp, bc, bm)
    nRb, nCb = P // bp, C // bc
    codes = (dense_codes(nRb, nCb) if tile_ids is None
             else slot_codes(tile_ids))
    assert codes.shape[0] == nRb, (codes.shape, nRb)

    ref_spec = pl.BlockSpec((bp, 1), lambda i, s, k, c: (i, 0))
    cand_spec = pl.BlockSpec((bm, bc),
                             lambda i, s, k, c: (k, _block_of(c[i, s])))
    cid_spec = pl.BlockSpec((1, bc), lambda i, s, k, c: (0, _block_of(c[i, s])))
    out_spec = pl.BlockSpec((bp, bc),
                            lambda i, s, k, c: (i, _block_of(c[i, s])))
    f32, i32 = jnp.float32, jnp.int32
    out_specs = [out_spec, out_spec]
    out_shape = [jax.ShapeDtypeStruct((P, C), f32),
                 jax.ShapeDtypeStruct((P, C), i32)]
    if rows:
        out_specs.append(pl.BlockSpec((1, 1, 128),
                                      lambda i, s, k, c: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nRb, 1, 128), i32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nRb, codes.shape[1], Mc // bm),
        in_specs=[ref_spec] * 5 + [cand_spec] * 3
        + [cid_spec, cand_spec, _SMEM, _ANY, _ANY],
        out_specs=out_specs)
    rx, ry, rt, rid, rok = ref_ops
    cx, cy, ct, cid, cok = cand_ops
    kernel = (functools.partial(_join_kernel, rows=rows, M=M) if rows
              else _join_kernel)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases={12: 0, 13: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(codes, _col(rx, f32), _col(ry, f32), _col(rt, f32),
      _col(rid, i32), _col(rok, i32), cx.astype(f32).T,
      cy.astype(f32).T, ct.astype(f32).T,
      cid.astype(i32).reshape(1, C), cok.astype(i32).T,
      _eps(*eps), jnp.zeros((P, C), f32), jnp.full((P, C), -1, i32))


@functools.partial(jax.jit, static_argnames=("bp", "bc", "bm", "interpret"))
def stjoin_pallas(ref_x, ref_y, ref_t, ref_id, ref_ok,
                  cand_x, cand_y, cand_t, cand_id, cand_ok,
                  eps_sp, eps_t, tile_ids=None, *, bp: int = 256,
                  bc: int = 128, bm: int = 128,
                  interpret: bool | None = None):
    """Returns ``(best_w [P, C] f32, best_idx [P, C] i32)``.

    ``P % bp == C % bc == Mc % bm == 0``.  ``tile_ids`` (``[P/bp, K]``,
    from ``repro.index.grid.compact_candidates``) restricts the sweep to
    the listed candidate blocks; the rest of the output keeps the empty
    value ``(0, -1)``, which is what the dense sweep yields there because
    pruning is conservative.
    """
    return _join_call(
        (ref_x, ref_y, ref_t, ref_id, ref_ok),
        (cand_x, cand_y, cand_t, cand_id, cand_ok), (eps_sp, eps_t),
        tile_ids, bp=bp, bc=bc, bm=bm, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("rows", "M", "bc", "bm", "interpret"))
def stjoin_dtj_pallas(ref_x, ref_y, ref_t, ref_id, ref_ok,
                      cand_x, cand_y, cand_t, cand_id, cand_ok,
                      eps_sp, eps_t, delta_t, tile_ids=None, *, rows: int,
                      M: int, bc: int = 128, bm: int = 128,
                      interpret: bool | None = None):
    """``stjoin_pallas`` with DTJ's Refine in the kernel: ``(best_w, best_idx,
    matches)``, the matches in runs shorter than ``delta_t`` dropped
    (``geometry.filter_delta_t``'s result, bit for bit) and ``matches``
    ``[2]`` i32 the count of matches before and after the refine.

    Ref blocks are ``bp = rows * M`` points, ``rows`` whole trajectory
    rows of ``M`` (``P % bp == 0``), so a run never spans two blocks;
    ``tile_ids`` is a plan built for that block.
    """
    w, idx, cnt = _join_call(
        (ref_x, ref_y, ref_t, ref_id, ref_ok),
        (cand_x, cand_y, cand_t, cand_id, cand_ok),
        (eps_sp, eps_t, delta_t), tile_ids, bp=rows * M, bc=bc, bm=bm,
        interpret=interpret, rows=rows, M=M)
    return w, idx, jnp.sum(cnt[:, 0, :2], axis=0)


# ---------------------------------------------------------------------------
# Fused pass 1: votes + packed neighbor words
# ---------------------------------------------------------------------------


def _vote_kernel(codes, rx, ry, rt, rid, rok, cx, cy, ct, cid, cok, eps,
                 out_vote, *outs, rows: int, M: int):
    """``outs`` holds the packed-word accumulator only when the caller
    needs TSA2 neighbor sets (vote-only otherwise)."""
    i, s = pl.program_id(0), pl.program_id(1)
    code = codes[i, s]

    @pl.when(s == 0)
    def _init():
        out_vote[...] = jnp.zeros_like(out_vote)
        for o in outs:
            o[...] = jnp.zeros_like(o)

    @pl.when(code >= 0)
    def _tile():
        bp, bc = rx.shape[0], cx.shape[1]
        (w,) = _sweep(_refs(rx, ry, rt, rid, rok), (cx, cy, ct, cok),
                      cid[...], eps[0], eps[1],
                      (jnp.zeros((bp, bc), jnp.float32),), n=cx.shape[0])
        w = _run_refine(w, rt[...], rows, M, eps[2])
        out_vote[...] += jnp.sum(w, axis=1, keepdims=True)
        if outs:
            _pack_words(w, code, outs[0])


@functools.partial(
    jax.jit, static_argnames=("rows", "M", "bc", "with_words", "interpret"))
def stjoin_vote_fused_flat(ref_x, ref_y, ref_t, ref_id, ref_ok,
                           cand_x, cand_y, cand_t, cand_id, cand_ok,
                           eps_sp, eps_t, delta_t, tile_ids=None, *,
                           rows: int, M: int, bc: int = 128,
                           with_words: bool = True,
                           interpret: bool | None = None):
    """Fused pass 1: ``(vote [P] f32, words [P, C/32] uint32 | None)``.

    Ref points are flattened ``[P]`` with ``P = n_rows_total * M`` and
    block ``bp = rows * M`` (whole trajectory rows per block, as the
    in-kernel delta_t refine needs).  ``C`` must be a multiple of 32 and
    of ``bc``; ``bc`` divides 32 or is a multiple of it.
    ``with_words=False`` (the TSA1 path) skips the packed words entirely.
    ``tile_ids`` sweeps only the listed candidate blocks (same result).
    """
    if interpret is None:
        interpret = default_interpret()
    P = ref_x.shape[0]
    C, Mc = cand_x.shape
    bp = rows * M
    assert P % bp == 0 and C % bc == 0 and C % 32 == 0, (P, C, bp, bc)
    assert 32 % bc == 0 or bc % 32 == 0, bc
    nRb, W = P // bp, C // 32
    codes = (dense_codes(nRb, C // bc) if tile_ids is None
             else slot_codes(tile_ids))

    ref_spec = pl.BlockSpec((bp, 1), lambda i, s, c: (i, 0))
    cand_spec = pl.BlockSpec((Mc, bc), lambda i, s, c: (0, _block_of(c[i, s])))
    cid_spec = pl.BlockSpec((1, bc), lambda i, s, c: (0, _block_of(c[i, s])))
    out_specs = [pl.BlockSpec((bp, 1), lambda i, s, c: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((P, 1), jnp.float32)]
    if with_words:
        out_specs.append(pl.BlockSpec((bp, W), lambda i, s, c: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((P, W), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nRb, codes.shape[1]),
        in_specs=[ref_spec] * 5 + [cand_spec] * 3
        + [cid_spec, cand_spec, _SMEM],
        out_specs=out_specs)
    f32, i32 = jnp.float32, jnp.int32
    out = pl.pallas_call(
        functools.partial(_vote_kernel, rows=rows, M=M),
        grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(codes, _col(ref_x, f32), _col(ref_y, f32), _col(ref_t, f32),
      _col(ref_id, i32), _col(ref_ok, i32), cand_x.astype(f32).T,
      cand_y.astype(f32).T, cand_t.astype(f32).T,
      cand_id.astype(i32).reshape(1, C), cand_ok.astype(i32).T,
      _eps(eps_sp, eps_t, delta_t))
    vote = out[0].reshape(P)
    if not with_words:
        return vote, None
    return vote, jax.lax.bitcast_convert_type(out[1], jnp.uint32)


# ---------------------------------------------------------------------------
# Fused pass 2: raw similarity scatter, one slot block per tile
# ---------------------------------------------------------------------------


def _sim_kernel(codes, base, rx, ry, rt, rid, rok, rsub, cx, cy, ct, cid,
                cok, csub, eps, o0, out, w_scr, sub_scr, *, rows: int,
                M: int):
    """Tile (i, s) -> raw block ``out[r * ms + a, k, c]``: the summed
    refined weight of ref row r's sub-slot ``a`` matched into candidate
    c's sub-slot ``k``.  Each cell is summed over the row's points in
    ascending order, the order of the materializing scatter-add."""
    del o0                        # aliased zeros (unvisited blocks)
    i, s = pl.program_id(0), pl.program_id(1)
    live = codes[i, s] >= 0

    @pl.when(live | (s == 0))
    def _init():
        out[...] = jnp.zeros_like(out)

    @pl.when(live)
    def _tile():
        bp, bc = w_scr.shape
        ms = out.shape[1]
        w, sub = _sweep(
            _refs(rx, ry, rt, rid, rok), (cx, cy, ct, cok), cid[...],
            eps[0], eps[1], (jnp.zeros((bp, bc), jnp.float32),
                             jnp.full((bp, bc), -1, jnp.int32)),
            n=cx.shape[0], csub=csub)
        w_scr[...] = _run_refine(w, rt[...], rows, M, eps[2])
        sub_scr[...] = sub
        _scatter(out, rsub, w_scr, sub_scr, rows=rows, M=M)


def _scatter(out, rsub, w, sub, *, rows: int, M: int):
    """Add the tile's ``[bp, bc]`` best weights ``w`` into the raw block
    ``out [rows * ms, ms, bc]``: ref point ``p = r * M + m`` (sub-slot
    ``rsub[0, 0, p]``, skipped when -1) into row ``r * ms + a``, candidate
    sub-slot ``sub[p]`` (-1: nothing).  Rows, then points in ascending
    order: the order of the materializing scatter-add."""
    ms = out.shape[1]
    k = jax.lax.broadcasted_iota(jnp.int32, (ms, 1), 0)

    def row(r, carry):
        def point(m, carry):
            p = r * M + m
            a = rsub[0, 0, p]

            @pl.when(a >= 0)
            def _add():
                hit = k == sub[pl.ds(p, 1), :]                   # [ms, bc]
                out[r * ms + a] += jnp.where(hit, w[pl.ds(p, 1), :], 0.0)
            return carry
        return jax.lax.fori_loop(0, M, point, carry)

    jax.lax.fori_loop(0, rows, row, 0)


def _sim_call(ref_ops, rsub, cand_ops, csub, eps, codes, base, *, rows: int,
              M: int, ms: int, bc: int, n_out_blocks: int,
              interpret: bool):
    """Raw blocks for the ref blocks ``base[0] + [0, codes.shape[0])`` and
    the candidate blocks ``codes`` names; output column block
    ``code - base[1]``.  Returns ``[nI * rows * ms, n_out_blocks * bc *
    ms]`` with slot ``(c, k)`` at column ``c * ms + k``."""
    P = ref_ops[0].shape[0]
    C, Mc = cand_ops[0].shape
    bp = rows * M
    nI, K = codes.shape
    R = rows * ms
    f32, i32 = jnp.float32, jnp.int32

    ref_spec = pl.BlockSpec((bp, 1), lambda i, s, c, b: (i + b[0], 0))
    rsub_spec = pl.BlockSpec((1, 1, bp),
                             lambda i, s, c, b: (i + b[0], 0, 0),
                             memory_space=pltpu.SMEM)
    cand_spec = pl.BlockSpec((Mc, bc),
                             lambda i, s, c, b: (0, _block_of(c[i, s])))
    cid_spec = pl.BlockSpec((1, bc), lambda i, s, c, b: (0, _block_of(c[i, s])))
    out_spec = pl.BlockSpec(
        (R, ms, bc), lambda i, s, c, b: (i, 0, _block_of(c[i, s]) - b[1]))
    out_shape = (nI * R, ms, n_out_blocks * bc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nI, K),
        in_specs=[ref_spec] * 5 + [rsub_spec] + [cand_spec] * 3
        + [cid_spec, cand_spec, cand_spec, _SMEM, _ANY],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((bp, bc), f32),
                        pltpu.VMEM((bp, bc), i32)])
    rx, ry, rt, rid, rok = ref_ops
    cx, cy, ct, cid, cok = cand_ops
    out = pl.pallas_call(
        functools.partial(_sim_kernel, rows=rows, M=M),
        grid_spec=grid_spec, out_shape=jax.ShapeDtypeStruct(out_shape, f32),
        input_output_aliases={15: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(codes, base, _col(rx, f32), _col(ry, f32), _col(rt, f32),
      _col(rid, i32), _col(rok, i32), rsub.astype(i32).reshape(-1, 1, bp),
      cx.astype(f32).T, cy.astype(f32).T, ct.astype(f32).T,
      cid.astype(i32).reshape(1, C), cok.astype(i32).T, csub.astype(i32).T,
      eps, jnp.zeros(out_shape, f32))
    return jnp.swapaxes(out, 1, 2).reshape(nI * R, -1)


def _sim_plan(P, C, tile_ids, rows, M, bc):
    """``(nRb, nCb, codes)`` for pass 2 over ``P`` reference points and
    ``C`` candidates.  A plan built on pass 1's words-padded candidate
    axis may list blocks past this one; those are pure padding (no valid
    point, so no survivor), dropped all the same."""
    assert P % (rows * M) == 0 and C % bc == 0, (P, C, rows, M, bc)
    nRb, nCb = P // (rows * M), C // bc
    if tile_ids is None:
        return nRb, nCb, dense_codes(nRb, nCb)
    return nRb, nCb, slot_codes(jnp.where(tile_ids < nCb, tile_ids, -1))


@functools.partial(
    jax.jit, static_argnames=("rows", "M", "ms", "bc", "interpret"))
def stjoin_sim_fused_flat(ref_ops, ref_sub, cand_ops, cand_sub, eps_sp,
                          eps_t, delta_t, tile_ids=None, *, rows: int,
                          M: int, ms: int, bc: int = 128,
                          interpret: bool | None = None):
    """Fused pass 2: the raw similarity scatter ``[P/M * ms, C * ms]``.

    ``ref_ops`` / ``cand_ops``: ``(x, y, t, id, ok)`` with ref arrays
    ``[P]`` (whole rows of ``M``, ``P % (rows*M) == 0``) and candidate
    arrays ``[C, Mc]`` (ids ``[C]``, ``C % bc == 0``).  ``ref_sub [P]`` /
    ``cand_sub [C, Mc]``: each point's local sub-slot in ``[0, ms)``, or
    -1 when unsegmented.  Row ``r * ms + a`` / column ``c * ms + k`` holds
    the summed refined best-match weight from sub-slot ``a`` of ref row
    ``r`` to sub-slot ``k`` of candidate ``c`` (``similarity_matrix``'s
    ``raw``).  ``tile_ids`` sweeps only the listed candidate blocks.
    """
    if interpret is None:
        interpret = default_interpret()
    _, nCb, codes = _sim_plan(ref_ops[0].shape[0], cand_ops[0].shape[0],
                              tile_ids, rows, M, bc)
    return _sim_call(ref_ops, ref_sub, cand_ops, cand_sub,
                     _eps(eps_sp, eps_t, delta_t), codes,
                     jnp.zeros((2,), jnp.int32), rows=rows, M=M, ms=ms,
                     bc=bc, n_out_blocks=nCb, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("rows", "M", "ms", "bc", "panel",
                              "interpret"))
def stjoin_sim_panel_flat(ref_ops, ref_sub, cand_ops, cand_sub, eps_sp,
                          eps_t, delta_t, p0, tile_ids=None, *, rows: int,
                          M: int, ms: int, panel: int, bc: int = 128,
                          interpret: bool | None = None):
    """One ``panel``-row slab of ``stjoin_sim_fused_flat``'s output in both
    orientations: ``(fwd, rev)`` with ``fwd[q, :] = raw[p0 + q, :]`` and
    ``rev[q, :] = raw[:, p0 + q]``.

    ``fwd`` sweeps only the ref blocks that hold the panel's rows (through
    ``tile_ids`` when given); ``rev`` sweeps every ref block against the
    candidate blocks that hold the panel's columns.  Each raw cell is
    computed by one tile in one fixed order, so both slabs equal the full
    sweep's rows and columns bit for bit.  ``p0`` may be traced (one trace
    serves every panel).
    """
    if interpret is None:
        interpret = default_interpret()
    nRb, nCb, codes = _sim_plan(ref_ops[0].shape[0], cand_ops[0].shape[0],
                                tile_ids, rows, M, bc)
    eps = _eps(eps_sp, eps_t, delta_t)
    p0 = jnp.asarray(p0, jnp.int32)
    kw = dict(rows=rows, M=M, ms=ms, bc=bc, interpret=interpret)

    R = rows * ms                                   # slot rows per ref block
    nI = min(nRb, -(-panel // R) + 1)
    i0 = jnp.clip(p0 // R, 0, nRb - nI)
    codes = jax.lax.dynamic_slice_in_dim(codes, i0, nI, axis=0)
    rows_raw = _sim_call(ref_ops, ref_sub, cand_ops, cand_sub, eps, codes,
                         jnp.stack([i0, 0]), n_out_blocks=nCb, **kw)
    fwd = jax.lax.dynamic_slice_in_dim(rows_raw, p0 - i0 * R, panel, axis=0)

    Rc = bc * ms                                    # slot cols per cand block
    nJ = min(nCb, -(-panel // Rc) + 1)
    j0 = jnp.clip(p0 // Rc, 0, nCb - nJ)
    cols_raw = _sim_call(ref_ops, ref_sub, cand_ops, cand_sub, eps,
                         dense_codes(nRb, nJ, j0), jnp.stack([0, j0]),
                         n_out_blocks=nJ, **kw)
    rev = jax.lax.dynamic_slice_in_dim(cols_raw, p0 - j0 * Rc, panel, axis=1)
    return fwd, rev.T


# ---------------------------------------------------------------------------
# Pass 2 from the materialized join: the same scatter, read from the cube
# ---------------------------------------------------------------------------


def _cube_sub(idx, start):
    """The sub-slot of each matched candidate point, without a gather.

    ``idx [bp, bc]``: the join's best point index (-1: no match);
    ``start [ms, bc]``: ``start[a, c]`` is candidate ``c``'s first valid
    point of sub-slot ``a`` or more (``Mc`` if none).  Sub-slots do not
    decrease along a row's valid points (``segmentation._finalize``), so a
    matched point's sub-slot is the count of boundaries ``a >= 1`` at or
    below it.
    """
    sub = jnp.zeros_like(idx)
    for a in range(1, start.shape[0]):
        sub += (idx >= start[a:a + 1, :]).astype(jnp.int32)
    return jnp.where(idx < 0, -1, sub)


def _sim_cube_kernel(codes, rsub, bw, bidx, start, o0, out, sub_scr, *,
                     rows: int, M: int):
    """``_sim_kernel``'s raw block from the join's tile ``(bw, bidx)``:
    the same scatter, in the same order, of weights already refined."""
    del o0                        # aliased zeros (unvisited blocks)
    i, s = pl.program_id(0), pl.program_id(1)
    live = codes[i, s] >= 0

    @pl.when(live | (s == 0))
    def _init():
        out[...] = jnp.zeros_like(out)

    @pl.when(live)
    def _tile():
        sub_scr[...] = _cube_sub(bidx[...], start[...])
        _scatter(out, rsub, bw, sub_scr, rows=rows, M=M)


@functools.partial(
    jax.jit, static_argnames=("rows", "M", "ms", "bc", "interpret"))
def stjoin_sim_fused_flat_cube(best_w, best_idx, ref_sub, cand_start,
                               tile_ids=None, *, rows: int, M: int, ms: int,
                               bc: int = 128, interpret: bool | None = None):
    """Pass 2 from a materialized join: ``stjoin_sim_fused_flat``'s raw
    scatter ``[P/M * ms, C * ms]``, bit for bit, read from the join's
    tiles instead of re-running their pair tests.

    ``best_w`` / ``best_idx`` ``[P, C]``: the join cube's ``[T * M, C]``
    view, with DTJ's refine already applied (the join's kernels do; at
    ``delta_t`` 0 it keeps every match), ``P % (rows * M) == 0`` and ``C
    % bc == 0``.  ``ref_sub [P]``: each ref point's sub-slot (-1 skips
    it); ``cand_start [ms, C]``: each candidate's sub-slot boundaries
    (``ops.cand_starts``).  ``tile_ids`` reads only the listed candidate
    blocks; the tiles it leaves out hold no match.
    """
    if interpret is None:
        interpret = default_interpret()
    P, C = best_w.shape
    _, _, codes = _sim_plan(P, C, tile_ids, rows, M, bc)
    bp, R = rows * M, rows * ms
    nI, K = codes.shape
    f32 = jnp.float32
    tile_spec = pl.BlockSpec((bp, bc), lambda i, s, c: (i, _block_of(c[i, s])))
    rsub_spec = pl.BlockSpec((1, 1, bp), lambda i, s, c: (i, 0, 0),
                             memory_space=pltpu.SMEM)
    start_spec = pl.BlockSpec((ms, bc),
                              lambda i, s, c: (0, _block_of(c[i, s])))
    out_spec = pl.BlockSpec(
        (R, ms, bc), lambda i, s, c: (i, 0, _block_of(c[i, s])))
    out_shape = (nI * R, ms, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nI, K),
        in_specs=[rsub_spec, tile_spec, tile_spec, start_spec, _ANY],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((bp, bc), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_sim_cube_kernel, rows=rows, M=M),
        grid_spec=grid_spec, out_shape=jax.ShapeDtypeStruct(out_shape, f32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(codes, ref_sub.astype(jnp.int32).reshape(-1, 1, bp),
      best_w.astype(f32), best_idx.astype(jnp.int32),
      cand_start.astype(jnp.int32), jnp.zeros(out_shape, f32))
    return jnp.swapaxes(out, 1, 2).reshape(nI * R, -1)
