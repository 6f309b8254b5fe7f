"""Mixture-of-Experts FFN with explicit expert parallelism.

Production path (``axis_name`` given, inside shard_map): tokens are routed
top-k, packed into fixed-capacity per-expert buffers, exchanged with a single
``lax.all_to_all`` over the 'model' mesh axis (EP), processed as dense
[E_local, cap, D] GEMMs on the expert owners, and returned with the inverse
all_to_all — the canonical EP schedule whose collective bytes are visible to
the roofline pass.

Fallback path (``axis_name=None``): identical math on one device (m=1), used
by smoke tests and the reference oracle.

Capacity: ``C = ceil(N*k/E * capacity_factor)``; overflow tokens are dropped
(their gate mass is lost — standard drop-token semantics, surfaced via the
returned ``dropped`` fraction).  Experts are padded up to a multiple of the
EP degree; padded experts are masked out of the router.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import MoEConfig

COMPUTE_DTYPE = jnp.bfloat16


def padded_experts(cfg: MoEConfig, ep_degree: int) -> int:
    return -(-cfg.n_experts // ep_degree) * ep_degree


def init_moe(key, d_model: int, cfg: MoEConfig, ep_degree: int = 1):
    E = padded_experts(cfg, ep_degree)
    ks = jax.random.split(key, 7)
    s = 1.0 / jnp.sqrt(d_model)
    p = {
        "router": jax.random.normal(ks[0], (d_model, E), jnp.float32) * s,
        "w_gate": jax.random.normal(
            ks[1], (E, d_model, cfg.d_expert), jnp.float32) * s,
        "w_up": jax.random.normal(
            ks[2], (E, d_model, cfg.d_expert), jnp.float32) * s,
        "w_down": jax.random.normal(
            ks[3], (E, cfg.d_expert, d_model), jnp.float32)
        * (1.0 / jnp.sqrt(cfg.d_expert)),
    }
    if cfg.n_shared > 0:
        f = cfg.n_shared * cfg.d_expert
        p["ws_gate"] = jax.random.normal(ks[4], (d_model, f), jnp.float32) * s
        p["ws_up"] = jax.random.normal(ks[5], (d_model, f), jnp.float32) * s
        p["ws_down"] = jax.random.normal(
            ks[6], (f, d_model), jnp.float32) * (1.0 / jnp.sqrt(f))
    return p


def _capacity(n_tokens: int, k: int, E: int, factor: float) -> int:
    c = int(n_tokens * k / E * factor) + 1
    return -(-c // 4) * 4


def _quant_dispatch(buf):
    """Per-row symmetric int8 quantization for the EP all_to_all payload
    (DeepSeek-V3-style low-precision dispatch): 2x fewer bytes on the wire
    vs bf16; scales ride along as f32 per (expert, slot)."""
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(buf.astype(jnp.float32) / scale), -127,
                 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant_dispatch(q, scale):
    return (q.astype(jnp.float32) * scale).astype(COMPUTE_DTYPE)


def moe_ffn(p, x, cfg: MoEConfig, *, axis_name: str | None = None,
            quantize_dispatch: bool = False,
            shared_sharded: bool = False):
    """x: [B, L, D] (device-local when inside shard_map).
    Returns (y, aux_loss, dropped_fraction)."""
    B, L, D = x.shape
    N = B * L
    xt = x.reshape(N, D).astype(COMPUTE_DTYPE)
    m = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    E = p["router"].shape[1]
    E_loc = E // m
    k = cfg.top_k
    C = _capacity(N, k, E, cfg.capacity_factor)

    # ---- routing ----
    logits = (xt @ p["router"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
    if cfg.n_experts < E:                       # mask padded experts
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)           # [N, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # switch-style load-balance aux: E * sum_e f_e * p_e
    onehot_top1 = jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32)
    f_e = onehot_top1.mean(0)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)

    # ---- dispatch bookkeeping (sort-based ranking) ----
    flat_e = eidx.reshape(-1)                   # [N*k]
    flat_g = gates.reshape(-1).astype(COMPUTE_DTYPE)
    src_row = jnp.arange(N * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(N * k, dtype=jnp.int32) - first[sorted_e]
    rank = jnp.zeros((N * k,), jnp.int32).at[order].set(rank_sorted)
    keep = rank < C
    dropped = 1.0 - keep.mean()

    dst_e = jnp.where(keep, flat_e, E)          # E = garbage bin row
    dst_c = jnp.where(keep, rank, 0)

    buf = jnp.zeros((E + 1, C, D), COMPUTE_DTYPE)
    buf = buf.at[dst_e, dst_c].set(xt[src_row], mode="drop")
    buf = buf[:E]                               # [E, C, D]

    # ---- EP exchange ----
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    if axis_name is not None:
        if quantize_dispatch:
            q, scale = _quant_dispatch(buf)
            qs = lax.all_to_all(q.reshape(m, E_loc, C, D), axis_name,
                                split_axis=0, concat_axis=0)
            ss = lax.all_to_all(scale.reshape(m, E_loc, C, 1), axis_name,
                                split_axis=0, concat_axis=0)
            recv = _dequant_dispatch(qs, ss)
        else:
            send = buf.reshape(m, E_loc, C, D)
            recv = lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=0)
        # [m(src), E_loc, C, D] -> [E_loc, m*C, D]
        hbuf = jnp.moveaxis(recv, 0, 1).reshape(E_loc, m * C, D)
    else:
        hbuf = buf

    g = jnp.einsum("ecd,edf->ecf", hbuf, w_gate.astype(COMPUTE_DTYPE))
    u = jnp.einsum("ecd,edf->ecf", hbuf, w_up.astype(COMPUTE_DTYPE))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(COMPUTE_DTYPE))

    if axis_name is not None:
        back = jnp.moveaxis(out.reshape(E_loc, m, C, D), 1, 0)
        ret = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0)
        ret = ret.reshape(E, C, D)
    else:
        ret = out

    # ---- combine ----
    vals = ret[jnp.clip(dst_e, 0, E - 1), dst_c]             # [N*k, D]
    vals = vals * (flat_g * keep.astype(COMPUTE_DTYPE))[:, None]
    y = jnp.zeros((N, D), COMPUTE_DTYPE).at[src_row].add(vals)

    # ---- shared experts (always-on) ----
    if "ws_gate" in p:
        sg = jax.nn.silu(xt @ p["ws_gate"].astype(COMPUTE_DTYPE))
        su = xt @ p["ws_up"].astype(COMPUTE_DTYPE)
        ysh = (sg * su) @ p["ws_down"].astype(COMPUTE_DTYPE)
        if shared_sharded and axis_name is not None:
            # column-sharded shared experts under EP: partial sums
            ysh = lax.psum(ysh, axis_name)
        y = y + ysh

    return y.reshape(B, L, D).astype(x.dtype), aux, dropped


def moe_ffn_shard_map(p, x, cfg: MoEConfig, mesh, dp_axes: tuple,
                      model_axis: str = "model",
                      quantize_dispatch: bool = False):
    """EP wrapper: runs ``moe_ffn`` inside shard_map on the ambient mesh so
    the dispatch/return all_to_alls are real collectives over ``model``."""
    from jax.sharding import PartitionSpec as P

    bspec = dp_axes if len(dp_axes) != 1 else dp_axes[0]
    x_spec = P(bspec, None, None)

    def pspec(path_leaf_name, leaf):
        name = path_leaf_name
        if name in ("w_gate", "w_up", "w_down"):
            return P(*(("model",) + (None,) * (leaf.ndim - 1)))
        if name in ("ws_gate", "ws_up"):
            return P(None, "model") if leaf.shape[1] % mesh.shape[
                model_axis] == 0 else P()
        if name == "ws_down":
            return P("model", None) if leaf.shape[0] % mesh.shape[
                model_axis] == 0 else P()
        return P()

    p_specs = {k: pspec(k, v) for k, v in p.items()}
    all_axes = tuple(dp_axes) + (model_axis,)

    shared_sharded = ("ws_gate" in p and p["ws_gate"].shape[1]
                      % mesh.shape[model_axis] == 0)

    def body(p_l, x_l):
        y, aux, dropped = moe_ffn(p_l, x_l, cfg, axis_name=model_axis,
                                  quantize_dispatch=quantize_dispatch,
                                  shared_sharded=shared_sharded)
        aux = lax.pmean(aux, all_axes)
        dropped = lax.pmean(dropped, all_axes)
        return y, aux, dropped

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P(), P()), check_vma=False)
    return fn(p, x)


def moe_ffn_dense_ref(p, x, cfg: MoEConfig):
    """Oracle: computes every expert densely and combines with router
    weights — no capacity, no drops.  For tests only (O(E) compute)."""
    B, L, D = x.shape
    xt = x.reshape(B * L, D).astype(jnp.float32)
    E = p["router"].shape[1]
    logits = xt @ p["router"]
    if cfg.n_experts < E:
        logits = jnp.where(jnp.arange(E)[None] >= cfg.n_experts, -1e30,
                           logits)
    probs = jax.nn.softmax(logits, -1)
    gates, eidx = lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    g = jnp.einsum("nd,edf->enf", xt, p["w_gate"])
    u = jnp.einsum("nd,edf->enf", xt, p["w_up"])
    h = jax.nn.silu(g) * u
    out = jnp.einsum("enf,efd->end", h, p["w_down"])     # [E, N, D]
    w = jnp.zeros((B * L, E)).at[
        jnp.arange(B * L)[:, None], eidx].add(gates)
    y = jnp.einsum("ne,end->nd", w, out)
    if "ws_gate" in p:
        y = y + (jax.nn.silu(xt @ p["ws_gate"]) * (xt @ p["ws_up"])) \
            @ p["ws_down"]
    return y.reshape(B, L, D).astype(x.dtype)
