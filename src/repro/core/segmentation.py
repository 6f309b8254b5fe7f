"""Neighborhood-aware trajectory segmentation — TSA1 & TSA2 (Algorithms 2, 3).

Both algorithms slide two adjacent windows ``W1 = [n-w, n-1]`` and
``W2 = [n, n+w-1]`` over a per-point signal and cut where the window
difference ``d[n]`` exceeds ``tau`` *and* is a local maximum.

Interpretation note (DESIGN.md §2.3): the paper's pseudocode line
``d[n] >= d_max`` with ``d_max`` the global maximum would allow a single cut
per trajectory, contradicting the text ("is locally maximized").  We implement
the text: a cut at ``n`` requires ``d[n] > tau`` and ``d[n] == max(d[n-w+1 ..
n+w-1])`` (strict left tie-break), the standard local-maxima picking of the
signal-segmentation literature the paper cites [16, 17].

TSA1 consumes the normalized voting vector (Eq. 5); TSA2 consumes per-point
neighbor *sets* (bit-packed) and uses windowed-union Jaccard dissimilarity.

All window math runs on the shared monoid sliding-window engine
(``repro.core.windows``, DESIGN.md §7): TSA1's window means are two reads
of one prefix sum, the local-max test is the two-pass block cummax, and
TSA2's set unions are the *same* block-scan trick applied to bit-packed
uint32 words — a dense packed-word sweep with no 32x bit-plane expansion
and no serial fold over the word axis.  The retained bit-plane
formulations (``_windowed_union``, ``_window_overlap_counts_bitplane``)
are regression oracles only.  ``tsa2(..., use_kernel=True)`` computes the
Jaccard signal through the fused Pallas kernel
(``repro.kernels.jaccard``) instead of the jnp engine — bit-identical
output either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import SubtrajSegmentation
from repro.core.windows import sliding_reduce, window_pair
from repro.kernels import exact_ratio


def _window_means(sig: jnp.ndarray, valid: jnp.ndarray, w: int):
    """Means of W1=[n-w, n-1] and W2=[n, n+w-1] at every n; [T, M] each."""
    x = jnp.where(valid, sig, 0.0)
    cnt = valid.astype(jnp.float32)
    s1, s2 = window_pair(x, w, "sum")
    c1, c2 = window_pair(cnt, w, "sum")
    return s1 / jnp.maximum(c1, 1.0), s2 / jnp.maximum(c2, 1.0)


def _local_max_cuts(d: jnp.ndarray, valid: jnp.ndarray, w: int, tau,
                    count: jnp.ndarray) -> jnp.ndarray:
    """Cut where d[n] > tau and d[n] is the max of its +-(w-1) window.

    The windowed maximum over [n-w+1, n+w-1] splits into the left-neighbor
    max (strict-left tie break: ``d[n]`` must beat it strictly) and the
    right-neighbor max (``>=`` suffices); both are O(M) prefix/suffix
    block-cummax windows from the shared engine instead of stacking 2w-1
    shifted copies (equality with the stacked formulation is pinned by
    ``tests/test_segmentation.py``)."""
    T, M = d.shape
    n = jnp.arange(M)
    # admissible positions: w+1 .. N-w-1 (1-based paper indexing -> w .. N-w-1)
    admissible = (n[None, :] >= w) & (n[None, :] <= count[:, None] - w - 1)
    d = jnp.where(valid & admissible, d, -jnp.inf)

    left = sliding_reduce(d, -(w - 1), -1, "max")
    right = sliding_reduce(d, 1, w - 1, "max")
    is_max = (d > left) & (d >= right)
    return is_max & (d > tau) & admissible & valid


def _finalize(cut: jnp.ndarray, valid: jnp.ndarray, score: jnp.ndarray,
              max_subs: int) -> SubtrajSegmentation:
    T, M = cut.shape
    first = valid & (jnp.cumsum(valid, axis=1) == 1)
    cut = (cut | first) & valid
    sub_local = jnp.clip(jnp.cumsum(cut, axis=1) - 1, 0, max_subs - 1)
    sub_local = jnp.where(valid, sub_local, -1).astype(jnp.int32)
    num = jnp.max(jnp.where(valid, sub_local, -1), axis=1) + 1
    return SubtrajSegmentation(
        cut=cut, sub_local=sub_local, num_subs=num.astype(jnp.int32),
        score=score)


def tsa1(norm_vote: jnp.ndarray, valid: jnp.ndarray, w: int, tau,
         max_subs: int = 8) -> SubtrajSegmentation:
    """Algorithm 2: density-change segmentation over the voting signal."""
    count = jnp.sum(valid, axis=1)
    m1, m2 = _window_means(norm_vote, valid, w)
    d = jnp.abs(m1 - m2)
    cuts = _local_max_cuts(d, valid, w, tau, count)
    return _finalize(cuts, valid, jnp.where(valid, d, 0.0), max_subs)


def _windowed_union(masks: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray):
    """Bit-plane oracle: OR-reduce packed masks over index window [lo, hi].

    Expands every uint32 word to 32 int32 bit-planes at once
    (``[T, M, W*32]``) and reduces via cumulative counts (OR of 0/1 bits
    == count > 0).  This is the pinned regression oracle for the packed
    windowed-OR production path — never call it from the pipeline.
    """
    T, M, W = masks.shape
    B = W * 32
    bits = ((masks[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1)
    bits = bits.astype(jnp.int32).reshape(T, M, B)               # [T, M, B]
    csum = jnp.cumsum(bits, axis=1)

    def take(c, idx):
        idxc = jnp.clip(idx, 0, M - 1)
        return jnp.take_along_axis(
            c, jnp.broadcast_to(idxc[None, :, None], (T, M, B)), axis=1)

    hi_v = jnp.where((hi >= 0)[None, :, None], take(csum, hi), 0)
    lo_v = jnp.where((lo > 0)[None, :, None], take(csum, lo - 1), 0)
    return (hi_v - lo_v) > 0                                     # [T, M, B]


def _window_overlap_counts_bitplane(masks: jnp.ndarray, w: int):
    """Bit-plane chunked W1/W2 intersection and union cardinalities.

    The pre-packed-engine production path, retained as a regression
    oracle and the bench comparator: a ``fori_loop`` folds one 32-bit
    plane chunk at a time, so peak extra memory is ``[T, M, 32]`` int32
    per word-step — 32x the packed masks — and the W iterations form a
    serial dependence chain.  Output equality with both the all-at-once
    expansion and the packed engine is pinned by
    ``tests/test_segmentation.py``.
    """
    T, M, W = masks.shape
    n = jnp.arange(M)

    def body(wi, carry):
        inter, union = carry
        word = jax.lax.dynamic_slice_in_dim(masks, wi, 1, axis=2)
        l1 = _windowed_union(word, n - w, n - 1)              # [T, M, 32]
        l2 = _windowed_union(word, n, n + w - 1)
        return (inter + jnp.sum(l1 & l2, axis=-1, dtype=jnp.int32),
                union + jnp.sum(l1 | l2, axis=-1, dtype=jnp.int32))

    zeros = jnp.zeros((T, M), jnp.int32)
    return jax.lax.fori_loop(0, W, body, (zeros, zeros))


def _window_overlap_counts(masks: jnp.ndarray, w: int):
    """Per-position W1/W2 set-union intersection and union cardinalities.

    Packed-word production path: bitwise OR is associative and idempotent,
    so the windowed set-union is the engine's two-pass block OR-scan
    applied directly to the ``[T, M, W]`` uint32 words, and the Jaccard
    numerator/denominator are popcount sums over the W word axis.  No
    bit-plane expansion, no serial fold over W: every intermediate is the
    size of the packed masks themselves (32x fewer elements than one
    bit-plane chunk, 32·W x fewer than the full expansion).
    """
    l1, l2 = window_pair(masks, w, "or")
    pc = jax.lax.population_count
    inter = jnp.sum(pc(l1 & l2), axis=-1, dtype=jnp.int32)
    union = jnp.sum(pc(l1 | l2), axis=-1, dtype=jnp.int32)
    return inter, union


def tsa2_signal(packed_masks: jnp.ndarray, w: int, *,
                impl: str = "packed") -> jnp.ndarray:
    """TSA2's windowed-Jaccard dissimilarity ``d[n]`` from packed masks.

    ``impl="packed"`` is the production packed-word engine;
    ``impl="bitplane"`` the retained 32x-expanded chunked oracle.  Both
    produce bit-identical ``d`` (same integer counts, same float ops) —
    the bench gates on exactly that plus the structural memory win.  The
    ratio is correctly rounded (``repro.kernels.exact_ratio``), as in the
    Pallas kernel, for at most ``RATIO_MAX_COUNT`` set members.
    """
    if impl == "packed":
        inter, union = _window_overlap_counts(packed_masks, w)
    elif impl == "bitplane":
        inter, union = _window_overlap_counts_bitplane(packed_masks, w)
    else:
        raise ValueError(f"unknown tsa2 signal impl {impl!r}")
    inter = inter.astype(jnp.float32)
    union = union.astype(jnp.float32)
    return jnp.where(union > 0,
                     1.0 - exact_ratio(inter, jnp.maximum(union, 1.0),
                                       32 * packed_masks.shape[-1]), 0.0)


def tsa2(packed_masks: jnp.ndarray, valid: jnp.ndarray, w: int, tau,
         max_subs: int = 8, *, use_kernel: bool = False) -> SubtrajSegmentation:
    """Algorithm 3: composition-change segmentation (windowed Jaccard).

    Masks at invalid positions are zeroed before the windowed union (the
    pipeline's packed masks are already zero there; direct callers may
    pass arbitrary words), so the jnp engine and the Pallas kernel
    (``use_kernel=True``) are bit-identical everywhere, score included.
    """
    count = jnp.sum(valid, axis=1)
    packed_masks = jnp.where(valid[..., None], packed_masks, jnp.uint32(0))
    if use_kernel:
        from repro.kernels.jaccard.ops import window_jaccard
        d = window_jaccard(packed_masks, valid, w=w)
    else:
        d = tsa2_signal(packed_masks, w)
    cuts = _local_max_cuts(d, valid, w, tau, count)
    return _finalize(cuts, valid, jnp.where(valid, d, 0.0), max_subs)


def segment(params_segmentation: str, *, norm_vote=None, packed_masks=None,
            valid=None, w: int = 10, tau=0.4, max_subs: int = 8,
            use_kernel: bool = False) -> SubtrajSegmentation:
    if params_segmentation == "tsa1":
        return tsa1(norm_vote, valid, w, tau, max_subs)
    if params_segmentation == "tsa2":
        return tsa2(packed_masks, valid, w, tau, max_subs,
                    use_kernel=use_kernel)
    raise ValueError(f"unknown segmentation {params_segmentation!r}")


segment_jit = jax.jit(segment, static_argnums=(0,),
                      static_argnames=("w", "max_subs", "use_kernel"))
