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
