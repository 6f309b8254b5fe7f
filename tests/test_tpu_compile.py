"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode accepts layouts Mosaic refuses (rank-1 blocks that are not
a multiple of 128, unsigned reductions, in-kernel gathers and scatters,
cumulative scans), so each kernel the ``materialize`` and ``fused`` plans
reach (the join with and without the in-kernel delta_t refine) is
compiled here with ``interpret=False`` at the widths of the
``dsc_brest`` deployment: M = 128 points per trajectory, 8 sub-slots per
trajectory, w = 20, and T = 2048 trajectories (S = 16,384 slots).  No chip
is needed: the topology is described, not attached, so nothing runs.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: a compile for a
described chip is written to the cache but cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import TrajectoryBatch
from repro.kernels.cluster import cluster as ck
from repro.kernels.jaccard import jaccard as jk
from repro.kernels.stjoin import ops as sops
from repro.kernels.stjoin import stjoin as sk

T, M, MS, W, WIN = 2048, 128, 8, 64, 20
P, C, S = T * M, T, T * MS
ROWS = 2                   # fused ref block: 2 rows of 128 points
K = 8                      # surviving tiles per ref block (pruned plans)
# one halo slab of the distributed materialize join: a rank's Mp points
# per trajectory against a candidate column block whose width is not a
# multiple of 128 (the wrapper pads it) and the 3*Mp-point halo concat
MP, TC = 64, 1000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument on one described chip,
    with the persistent cache off for the module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _refs(spec, n):
    f32 = jnp.float32
    return [spec((n,), f32)] * 3 + [spec((n,), jnp.int32),
                                     spec((n,), jnp.bool_)]


def _cands(spec, c, m):
    f32 = jnp.float32
    return [spec((c, m), f32)] * 3 + [spec((c,), jnp.int32),
                                       spec((c, m), jnp.bool_)]


def _batch(spec, n, m):
    """Abstract ``TrajectoryBatch`` leaves ``(x, y, t, valid, traj_id)``."""
    f32 = jnp.float32
    return [spec((n, m), f32)] * 3 + [spec((n, m), jnp.bool_),
                                       spec((n,), jnp.int32)]


def _kernel_calls(spec):
    """name -> (function, abstract args) for every main-path kernel."""
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    e = spec((), f32)
    ref, cand = _refs(spec, P), _cands(spec, C, M)
    sub_r, sub_c = spec((P,), i32), spec((C, M), i32)
    join_tiles = spec((P // 256, K), i32)
    fused_tiles = spec((P // (ROWS * M), K), i32)
    fused = dict(rows=ROWS, M=M, bc=128, interpret=False)
    sim = dict(fused, ms=MS)
    start = spec((MS, C), i32)
    vecs = [spec((S,), i32), spec((S,), b), spec((S,), b)]
    lists = [spec((S, 33), i32), spec((S, 33), f32)]
    return {
        "stjoin_dense": (
            lambda *a: sk.stjoin_pallas(*a, bp=256, bc=128, bm=128,
                                        interpret=False),
            [*ref, *cand, e, e]),
        "stjoin_pruned": (
            lambda *a: sk.stjoin_pallas(*a, bp=256, bc=128, bm=128,
                                        interpret=False),
            [*ref, *cand, e, e, join_tiles]),
        "stjoin_dtj_dense": (
            lambda *a: sk.stjoin_dtj_pallas(*a, rows=ROWS, M=M, bc=128,
                                            bm=128, interpret=False),
            [*ref, *cand, e, e, e]),
        "stjoin_dtj_pruned": (
            lambda *a: sk.stjoin_dtj_pallas(*a, rows=ROWS, M=M, bc=128,
                                            bm=128, interpret=False),
            [*ref, *cand, e, e, e, fused_tiles]),
        "vote_dense_tsa2": (
            lambda *a: sk.stjoin_vote_fused_flat(*a, **fused),
            [*ref, *cand, e, e, e]),
        "vote_dense_tsa1": (
            lambda *a: sk.stjoin_vote_fused_flat(*a, with_words=False,
                                                 **fused),
            [*ref, *cand, e, e, e]),
        "vote_pruned": (
            lambda *a: sk.stjoin_vote_fused_flat(*a, **fused),
            [*ref, *cand, e, e, e, fused_tiles]),
        "sim_dense": (
            lambda r, rs, c, cs, *a: sk.stjoin_sim_fused_flat(
                r, rs, c, cs, *a, **sim),
            [ref, sub_r, cand, sub_c, e, e, e]),
        "sim_pruned": (
            lambda r, rs, c, cs, *a: sk.stjoin_sim_fused_flat(
                r, rs, c, cs, *a, **sim),
            [ref, sub_r, cand, sub_c, e, e, e, fused_tiles]),
        "sim_panel_dense": (
            lambda r, rs, c, cs, *a: sk.stjoin_sim_panel_flat(
                r, rs, c, cs, *a, panel=128, **sim),
            [ref, sub_r, cand, sub_c, e, e, e, spec((), i32)]),
        "sim_panel_pruned": (
            lambda r, rs, c, cs, *a: sk.stjoin_sim_panel_flat(
                r, rs, c, cs, *a, panel=128, **sim),
            [ref, sub_r, cand, sub_c, e, e, e, spec((), i32), fused_tiles]),
        "sim_cube_dense": (
            lambda *a: sk.stjoin_sim_fused_flat_cube(*a, **sim),
            [spec((P, C), f32), spec((P, C), i32), sub_r, start]),
        "sim_cube_pruned": (
            lambda *a: sk.stjoin_sim_fused_flat_cube(*a, **sim),
            [spec((P, C), f32), spec((P, C), i32), sub_r, start,
             fused_tiles]),
        "cluster_round_scan": (
            lambda *a: ck.round_scan_pallas(*a, interpret=False),
            [spec((S, S), f32), *vecs, e]),
        "cluster_assign": (
            lambda *a: ck.assign_pallas(*a, interpret=False),
            [spec((S, S), f32), *vecs, e]),
        "topk_round_scan": (
            lambda *a: ck.topk_round_scan_pallas(*a, interpret=False),
            [*lists, *vecs, e]),
        "topk_assign": (
            lambda *a: ck.topk_assign_pallas(*a, interpret=False),
            [*lists, *vecs, e]),
        "jaccard": (
            lambda m: jk.jaccard_pallas(m, w=WIN, interpret=False),
            [spec((T, M, W), jnp.uint32)]),
        "stjoin_mesh_slab": (
            lambda *a: sops.best_match_join_kernel(
                TrajectoryBatch(*a[:5]), TrajectoryBatch(*a[5:10]), *a[10:],
                interpret=False),
            [*_batch(spec, T, MP), *_batch(spec, TC, 3 * MP), e, e]),
    }


KERNELS = ["stjoin_dense", "stjoin_pruned", "stjoin_dtj_dense",
           "stjoin_dtj_pruned", "vote_dense_tsa2",
           "vote_dense_tsa1", "vote_pruned", "sim_dense", "sim_pruned",
           "sim_panel_dense", "sim_panel_pruned", "sim_cube_dense",
           "sim_cube_pruned", "cluster_round_scan",
           "cluster_assign", "topk_round_scan", "topk_assign", "jaccard",
           "stjoin_mesh_slab"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(spec, name):
    fn, args = _kernel_calls(spec)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    # a kernel left in interpret mode would lower to plain HLO instead
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_list_is_complete(spec):
    assert sorted(_kernel_calls(spec)) == sorted(KERNELS)
