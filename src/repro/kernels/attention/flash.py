"""Pallas TPU kernel: fused (flash) GQA attention forward.

Motivation (EXPERIMENTS.md §Perf, smollm hillclimb): the XLA online-softmax
path keeps numerics right and peak memory low, but its score / probability
blocks still cross HBM between the two dots — for [B,L,H] = [1, 4096, 15]
that round-trip dominates the memory roofline term.  This kernel keeps the
whole (q-block x kv-block) working set in VMEM: scores, the running
(max, denom) and the output accumulator never leave the chip.

Grid: (B*KV, Lq/bq) — one program instance owns a q-block for one kv-head
group and scans the kv sequence in bk-sized slabs with the standard
online-softmax update.  Working set (bq=256, bk=512, G<=8, hd<=256):
    q block      bq*G*hd*4           =  2 MiB   (f32, G=8, hd=256)
    k/v slabs    2*bk*hd*4           =  1 MiB
    scores       bq*G*bk*4           =  4 MiB
    accumulators bq*G*(hd+2)*4       =  2 MiB
comfortably inside the ~16 MiB VMEM budget.

Supports causal masking, sliding windows, softcap and prefix-LM — the same
mask algebra as ``repro.models.layers._mask_block``.  Backward runs through
the jnp reference (``ops.flash_attention`` wraps with jax.custom_vjp-free
recompute); on real TPU a paired backward kernel would follow the same
tiling.  Validated against ref.py in tests/test_flash_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, meta_ref, o_ref, *,
            bk: int, softcap: float | None):
    """One q-block vs the full kv sequence (scanned in bk slabs)."""
    q = q_ref[0]              # [bq, G, hd]
    bq, G, hd = q.shape
    M = k_ref.shape[1]
    qpos = qpos_ref[...]      # [bq]
    window = meta_ref[0]
    prefix = meta_ref[1]
    max_kv = meta_ref[2]

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * bk, bk), :]                      # [bk, hd]
        v = v_ref[0, pl.ds(i * bk, bk), :]
        kpos = kpos_ref[pl.ds(i * bk, bk)]

        s = jnp.einsum("qgh,kh->qgk", q, k)                    # [bq, G, bk]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        causal = kpos[None, :] <= qpos[:, None]
        causal &= kpos[None, :] > (qpos[:, None] - window)
        bidir = (kpos[None, :] < prefix) & (qpos[:, None] < prefix)
        ok = causal | bidir
        ok &= kpos[None, :] <= max_kv
        s = jnp.where(ok[:, None, :], s.astype(jnp.float32), NEG)

        m_new = jnp.maximum(m, s.max(axis=-1))
        scale = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * scale + p.sum(axis=-1)
        acc_new = acc * scale[..., None] + jnp.einsum(
            "qgk,kh->qgh", p.astype(v.dtype), v)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, G), NEG, jnp.float32)
    l0 = jnp.zeros((bq, G), jnp.float32)
    a0 = jnp.zeros((bq, G, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, M // bk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bk", "softcap", "interpret"))
def flash_attention_fwd(q, k, v, q_positions, kv_positions, window, prefix,
                        max_kv, *, bq: int = 256, bk: int = 512,
                        softcap: float | None = None,
                        interpret: bool | None = None):
    """q: [B, Lq, KV, G, hd]; k/v: [B, M, KV, hd].  Returns [B, Lq, KV, G,
    hd].  Positions are int32 vectors; window/prefix/max_kv int32 scalars
    (use huge values to disable)."""
    if interpret is None:
        interpret = default_interpret()
    B, Lq, KV, G, hd = q.shape
    M = k.shape[1]
    bq = min(bq, Lq)
    while Lq % bq:
        bq //= 2
    bk = min(bk, M)
    while M % bk:
        bk //= 2

    meta = jnp.stack([jnp.asarray(window, jnp.int32),
                      jnp.asarray(prefix, jnp.int32),
                      jnp.asarray(max_kv, jnp.int32)])

    # flatten (B, KV) into the grid's first axis
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B * KV, Lq, G, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, M, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, M, hd)

    grid = (B * KV, Lq // bq)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((1, M, hd), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, M, hd), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((bq,), lambda h, i: (i,)),
            pl.BlockSpec((M,), lambda h, i: (0,)),
            pl.BlockSpec((3,), lambda h, i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda h, i: (h, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Lq, G, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, q_positions.astype(jnp.int32),
      kv_positions.astype(jnp.int32), meta)
    return out.reshape(B, KV, Lq, G, hd).transpose(0, 2, 1, 3, 4)
