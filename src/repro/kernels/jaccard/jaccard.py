"""Pallas TPU kernel: TSA2's sliding-window set-union Jaccard dissimilarity.

This is the segmentation kernel package's fused TSA2 sweep: packed
windowed-OR + popcount -> Jaccard dissimilarity ``d[n]`` in one pass.

Input: per-point neighbor sets, bit-packed as uint32 words ``[T, M, W]``
(bit c of word c//32 set iff candidate trajectory c matches the point).
For every position n the kernel forms the unions

    l1 = OR of masks[n-w .. n-1]        l2 = OR of masks[n .. n+w-1]

and emits ``d[n] = 1 - popcount(l1 & l2) / popcount(l1 | l2)`` (Algorithm 3
line 7).  The window OR uses the same idempotent-monoid decomposition as
``repro.core.windows`` (DESIGN.md §7), in its in-register doubling form:
a trailing window of length ``c`` doubles to ``c + min(c, w - c)`` with a
single static shift+OR, so the full window costs ``ceil(log2 w)``
shift+OR steps over the resident ``[bt, M, W]`` slab — pure
integer VPU work (no MXU, no gathers), ``O(M * log(w) * W)`` ops per
trajectory where the bit-expanded reference spends ``O(M * w * W * 32)``.
``l1`` is the trailing window shifted by one; ``l2`` is the same
doubling run with the shifts reversed (a leading window).  Every shift is
a roll along the point axis plus an iota mask.

Block layout (stjoin tile conventions): the grid walks blocks of ``bt``
whole trajectories; the whole point axis is resident per program instance
(windows straddle any smaller tiling), so a block is ``[bt, M, W]``
(bt=8, M<=512, W<=32 -> 512 KiB of VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import (default_interpret, exact_ratio,
                           shift_points as _shift)


def _window_or(masks, w: int, sign: int):
    """OR over ``w`` consecutive positions ending (``sign=+1``) or
    starting (``sign=-1``) at each position, by doubling: a window of
    length ``c`` grows to ``c + min(c, w - c)`` with one shift+OR."""
    acc, c = masks, 1
    while c < w:
        step = min(c, w - c)
        acc = acc | _shift(acc, sign * step)
        c += step
    return acc


def _kernel(masks_ref, out_d_ref, *, w: int):
    masks = masks_ref[...]                         # [bt, M, W] int32 words
    l1 = _shift(_window_or(masks, w, 1), 1)        # W1 = [n-w, n-1]
    l2 = _window_or(masks, w, -1)                  # W2 = [n, n+w-1]

    pc = jax.lax.population_count
    inter = jnp.sum(pc(l1 & l2), axis=-1).astype(jnp.float32)
    union = jnp.sum(pc(l1 | l2), axis=-1).astype(jnp.float32)
    out_d_ref[...] = jnp.where(
        union > 0,
        1.0 - exact_ratio(inter, jnp.maximum(union, 1.0),
                          32 * masks_ref.shape[-1]), 0.0)


@functools.partial(jax.jit, static_argnames=("w", "bt", "interpret"))
def jaccard_pallas(masks: jnp.ndarray, *, w: int, bt: int = 8,
                   interpret: bool | None = None) -> jnp.ndarray:
    """[T, M, W] packed masks -> [T, M] window Jaccard dissimilarity.

    The words are carried as int32 (Mosaic reduces no unsigned type);
    OR, shifts and popcount are bit-for-bit the same on either.  The
    ratio is correctly rounded (``exact_ratio``) for sets of at most
    ``RATIO_MAX_COUNT`` members (``W <= 1024``).
    """
    if interpret is None:
        interpret = default_interpret()
    T, M, W = masks.shape
    padT = (-T) % bt
    if padT:
        masks = jnp.pad(masks, ((0, padT), (0, 0), (0, 0)))
    Tp = T + padT

    out = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=(Tp // bt,),
        in_specs=[pl.BlockSpec((bt, M, W), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bt, M), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, M), jnp.float32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(masks, jnp.int32))
    return out[:T]
