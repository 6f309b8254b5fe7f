"""Pallas TPU kernel: weighted LCSS dynamic program (Eq. 2), wavefront form.

The recurrence (max-weight common subsequence under the (eps_sp, eps_t)
matching predicate; DESIGN.md §2.2):

    L[i, j] = max(L[i-1, j], L[i, j-1], L[i-1, j-1] + w[i, j])

with ``w[i, j] = 1 - d_sp/eps_sp`` for matching pairs and -inf otherwise.
A second channel runs the same recurrence with unit weights — the *classical*
LCSS length of Eq. 1.

TPU adaptation: the DP has a strict diagonal dependency, useless for the MXU
but perfectly vectorizable along anti-diagonals on the VPU.  The host wrapper
*shears* the weight matrix (row i shifted right by i) so that every
anti-diagonal ``d = i + j`` becomes a contiguous column of the sheared tensor
``Ws[i, d]`` — turning the wavefront into ``N+M-1`` vectorized column steps
with two carried diagonal vectors, no strided VMEM access.

Block layout: one (pair) program instance owns ``Ws[2, N, D]`` in VMEM
(N=M=128 -> 2*128*256*4B = 256 KiB) plus three [2, N] carries; the grid is
the batch of pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG = -1e30


def _kernel(ws_ref, out_ref):
    ws = ws_ref[...]                      # [1, 2, N, D]
    _, ch, N, D = ws.shape
    ws = ws.reshape(ch, N, D)

    def shift_down(v):                    # index i reads previous i-1
        return jnp.concatenate(
            [jnp.zeros((ch, 1), v.dtype), v[:, :-1]], axis=1)

    def body(d, carry):
        d1, d2 = carry                    # diagonals d-1, d-2; [2, N]
        w_col = jax.lax.dynamic_slice(ws, (0, 0, d), (ch, N, 1))[..., 0]
        cand = shift_down(d2) + w_col     # match at (i, d-i)
        d0 = jnp.maximum(jnp.maximum(d1, shift_down(d1)), cand)
        d0 = jnp.maximum(d0, 0.0)         # L >= 0 everywhere
        return d0, d1

    zero = jnp.zeros((ch, N), jnp.float32)
    dlast, _ = jax.lax.fori_loop(0, D, body, (zero, zero))
    out_ref[...] = dlast[:, -1][None, :]  # L at (N-1, M-1), both channels


@functools.partial(jax.jit, static_argnames=("interpret",))
def lcss_pallas(ws: jnp.ndarray, *,
                interpret: bool | None = None) -> jnp.ndarray:
    """``ws``: [B, 2, N, D] sheared weights (channel 0 weighted, 1 unit).
    Returns scores [B, 2]."""
    if interpret is None:
        interpret = default_interpret()
    B, ch, N, D = ws.shape
    assert ch == 2
    return pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 2, N, D), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 2), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 2), jnp.float32),
        interpret=interpret,
    )(ws)


def shear_weights(rx, ry, rt, rv, sx, sy, st, sv, eps_sp, eps_t):
    """Host-side (jnp) preparation: match weights, sheared to [B, 2, N, D].

    Inputs are [B, N] / [B, M] point coordinates + validity.
    """
    B, N = rx.shape
    M = sx.shape[1]
    dx = rx[:, :, None] - sx[:, None, :]
    dy = ry[:, :, None] - sy[:, None, :]
    dt = jnp.abs(rt[:, :, None] - st[:, None, :])
    d = jnp.sqrt(dx * dx + dy * dy)
    ok = (d <= eps_sp) & (dt <= eps_t) & rv[:, :, None] & sv[:, None, :]
    w = jnp.where(ok, 1.0 - d / eps_sp, NEG)              # [B, N, M]
    u = jnp.where(ok, 1.0, NEG)

    D = N + M - 1
    # shear: Ws[b, i, i + j] = w[b, i, j]
    cols = jnp.arange(N)[:, None] + jnp.arange(M)[None, :]   # [N, M]
    ws = jnp.full((B, 2, N, D), NEG, jnp.float32)
    bi = jnp.arange(B)[:, None, None]
    ii = jnp.broadcast_to(jnp.arange(N)[None, :, None], (B, N, M))
    cc = jnp.broadcast_to(cols[None], (B, N, M))
    ws = ws.at[bi, 0, ii, cc].set(w)
    ws = ws.at[bi, 1, ii, cc].set(u)
    return ws
