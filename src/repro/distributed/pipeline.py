"""Pipeline parallelism over the 'pod' axis (GPipe schedule, differentiable).

Design: on a multi-pod mesh the 'pod' axis crosses DCN, where bandwidth is
an order of magnitude below ICI — the natural mapping is *pipeline* stages
per pod (activations cross DCN once per microbatch, instead of gradient
all-reduces every step).  This module implements a GPipe forward schedule
with ``lax.ppermute`` between stages inside ``shard_map``; JAX reverse-mode
differentiates through the ppermutes (the backward schedule is the reversed
pipeline), so the same code trains.

The schedule runs ``n_micro + n_stages - 1`` ticks; each tick every stage
processes one microbatch slot (bubble slots compute on zeros — the classic
GPipe bubble, fraction (S-1)/(M+S-1)).

Usage (see tests/test_pipeline.py):

    fn = pipeline_apply(stage_fn, mesh, stage_axis="pod", n_micro=4)
    y = fn(stage_params, x)     # stage_params sharded over 'pod' on axis 0
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def pipeline_apply(stage_fn: Callable, mesh, *, stage_axis: str = "pod",
                   n_micro: int, data_axes: tuple = ("data",)):
    """Build a pipelined forward over ``stage_axis``.

    ``stage_fn(params_stage, x_micro) -> y_micro`` is one stage's compute
    (e.g. a block of layers).  ``x`` is [B, ...] with B divisible by
    n_micro; stage 0 feeds microbatches in, stage S-1 collects outputs.
    Returns a function (stage_params, x) -> y where ``stage_params`` leaves
    have a leading stage dimension.
    """
    S = mesh.shape[stage_axis]

    def body(params_st, x):
        # params_st leaves arrive as [1, ...] (this stage's shard) — strip
        # the stage dim; x: full local batch on every stage (only stage 0's
        # copy is fed in).
        params_st = jax.tree.map(lambda a: a[0], params_st)
        sid = lax.axis_index(stage_axis)
        B = x.shape[0]
        assert B % n_micro == 0
        mb = B // n_micro
        micro = x.reshape(n_micro, mb, *x.shape[1:])

        n_ticks = n_micro + S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, out = carry          # buf: [mb, ...] stage input register
            # stage 0 loads microbatch t (if in range)
            feed = jnp.where(t < n_micro,
                             micro[jnp.clip(t, 0, n_micro - 1)],
                             jnp.zeros_like(buf))
            cur = jnp.where(sid == 0, feed, buf)
            y = stage_fn(params_st, cur)
            # last stage stores its result at slot t - (S - 1)
            slot = t - (S - 1)
            store = (sid == S - 1) & (slot >= 0)
            out = jax.lax.cond(
                store,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(slot, 0),) + (0,) * y.ndim),
                lambda o: o, out)
            nxt = lax.ppermute(y, stage_axis, fwd_perm)
            return (nxt, out), None

        buf0 = jnp.zeros_like(micro[0])
        out0 = jnp.zeros((n_micro,) + micro.shape[1:], x.dtype)
        (_, out), _ = lax.scan(tick, (buf0, out0),
                               jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all stages so the
        # result is replicated over the pipeline axis (masked psum)
        out = lax.psum(
            jnp.where(sid == S - 1, out, jnp.zeros_like(out)), stage_axis)
        return out.reshape(B, *x.shape[1:])

    dspec = data_axes if len(data_axes) != 1 else data_axes[0]
    in_specs = (P(stage_axis), P(dspec))
    out_specs = P(dspec)

    def wrapped(stage_params, x):
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: in_specs[0], stage_params),
                      in_specs[1]),
            out_specs=out_specs, check_vma=False)
        return fn(stage_params, x)

    return wrapped


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
