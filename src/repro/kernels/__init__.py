"""Pallas TPU kernels for the DSC hot spots.

Each kernel package ships three modules:
  <name>.py — the pl.pallas_call kernel with explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper (interpret mode off TPU)
  ref.py    — the pure-jnp oracle used by tests/benchmarks

Kernels:
  stjoin    — best-match spatiotemporal join (the paper's dominant cost)
  cluster   — round-parallel greedy clustering (Algorithm 4) round scan +
              claim-max over [S, S] tiles
  lcss      — weighted-LCSS dynamic program (Eq. 2), anti-diagonal wavefront
  jaccard   — the TSA2 segmentation kernel: packed windowed-OR + popcount
              -> sliding-window Jaccard d[n] in one sweep
              (``seg_use_kernel=True`` from every pipeline entry point)
  attention — flash attention for the LM serving path (optional)
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def default_interpret() -> bool:
    """Interpret kernels in Python unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def shift_points(x, k: int, fill=0):
    """In-kernel ``x[:, m - k]`` along axis 1 (``k < 0`` reads
    ``m + |k|``), ``fill`` where that falls outside the row: a roll plus
    an iota mask, which Mosaic keeps in registers (it lowers no gather
    and no cumulative scan)."""
    M = x.shape[1]
    if abs(k) >= M:
        return jnp.full_like(x, fill)
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if k > 0:
        return jnp.where(pos >= k, pltpu.roll(x, k, 1), fill)
    return jnp.where(pos < M + k, pltpu.roll(x, M + k, 1), fill)


# Largest count ``exact_ratio`` divides exactly (15 bits): a count times
# an 8-bit third of a quotient's significand is then an exact float32
# product, and each partial residual fits in 24 bits.
RATIO_MAX_COUNT = 1 << 15


def round_quotient(num, den, q):
    """The float32 nearest ``num / den`` among ``q`` and its neighbours
    within two ulps, for whole-number float32 ``0 <= num <= den <=
    RATIO_MAX_COUNT``, ``den >= 1``, and ``q >= 0`` within two ulps of it.
    Each candidate ``q'`` is scored by its residual ``num - q' * den``,
    which is exact here: ``q'`` is split into three parts of 8
    significant bits, so each product with ``den`` is exact; the first
    difference is exact by Sterbenz's lemma, and the later ones because
    what is left is a multiple of ``q'``'s ulp below ``2**24`` of them."""
    f32, i32 = jnp.float32, jnp.int32
    bits = jax.lax.bitcast_convert_type(q, i32)

    def at(b):
        return jax.lax.bitcast_convert_type(b, f32)

    def residual(b):
        hi, hm = at(b & -(1 << 16)), at(b & -(1 << 8))
        return jnp.abs(((num - hi * den) - (hm - hi) * den)
                       - (at(b) - hm) * den)

    best, err = q, residual(bits)
    for k in (-2, -1, 1, 2):
        e = residual(bits + k)         # NaN below 0 (num == 0): never taken
        take = e < err
        best = jnp.where(take, at(bits + k), best)
        err = jnp.where(take, e, err)
    return best


def exact_ratio(num, den, max_count: int):
    """``num / den`` correctly rounded (see ``round_quotient``), on the
    TPU too, whose division by a refined reciprocal misses that by an ulp
    for about a quarter of the ratios of counts up to 2048 (v5e).  Equal
    ratios of different counts then give equal quotients, so comparisons
    of ratios tie where exact arithmetic ties.  ``max_count`` is the
    static bound on the counts: above ``RATIO_MAX_COUNT`` this is the
    plain quotient."""
    if max_count > RATIO_MAX_COUNT:
        return num / den
    return round_quotient(num, den, num / den)
