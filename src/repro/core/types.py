"""Core data model for Distributed Subtrajectory Clustering.

Everything is fixed-shape (TPU-friendly). The canonical layout is
*trajectory-major*: a batch of ``T`` trajectories, each padded to ``M``
timestamped points. Invalid slots carry ``valid == False`` and are ignored by
every operator.

Paper mapping
-------------
* ``TrajectoryBatch``        <- the input dataset ``D`` (Sec. 3)
* ``JoinResult``             <- the DTJ output: per reference point, the
                                best-matching point of every other trajectory
                                (the ``MatchingPoints`` lists, densified)
* ``SubtrajSegmentation``    <- the cutting-point vector CP[] (Problems 2)
* ``SubtrajTable``           <- the ST relation: (t_s, t_e, V, Card) per subtraj
* ``SimilarityMatrix``       <- the SP relation (adjacency lists, densified)
* ``TopKSim``                <- the SP relation kept sparse: per-row top-K
                                neighbor lists + an exactness certificate
* ``ClusteringResult``       <- the sets C (clusters) and O (outliers)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.utils.tree import pytree_dataclass, static_field


@pytree_dataclass
class DSCParams:
    """All parameters of the DSC pipeline (paper Table 1).

    ``alpha``/``k`` follow Sec. 6.1: they are expressed in standard deviations
    around the per-partition mean of the similarity / voting distribution
    (``alpha_sigma``, ``k_sigma``) unless absolute overrides are given.
    """

    eps_sp: float = 0.1        # spatial matching threshold epsilon_sp
    eps_t: float = 0.5         # temporal matching tolerance epsilon_t
    # minimum duration of a match (delta t); static, so whether the Refine
    # step runs is decided at trace time (DESIGN.md §3)
    delta_t: float = static_field(default=0.0)
    w: int = static_field(default=10)     # sliding-window size (samples)
    tau: float = 0.4           # segmentation threshold on window difference
    alpha_sigma: float = 0.0   # similarity threshold, in sigmas around mean
    k_sigma: float = 0.0       # voting threshold, in sigmas around mean
    alpha_abs: float = -1.0    # absolute override; active when >= 0
    k_abs: float = -1.0        # absolute override; active when >= 0
    # --- capacities (static; replace the paper's dynamic HashMaps/lists) ---
    max_subtrajs_per_traj: int = static_field(default=8)
    segmentation: str = static_field(default="tsa1")  # "tsa1" | "tsa2"


@pytree_dataclass
class TrajectoryBatch:
    """``T`` trajectories padded to ``M`` points, time-sorted within a row."""

    x: jnp.ndarray        # [T, M] float32
    y: jnp.ndarray        # [T, M] float32
    t: jnp.ndarray        # [T, M] float32 (seconds)
    valid: jnp.ndarray    # [T, M] bool
    traj_id: jnp.ndarray  # [T] int32 global trajectory ids (-1 = padding row)

    @property
    def num_trajs(self) -> int:
        return self.x.shape[0]

    @property
    def max_points(self) -> int:
        return self.x.shape[1]

    @property
    def count(self) -> jnp.ndarray:   # [T] valid points per trajectory
        return jnp.sum(self.valid, axis=1).astype(jnp.int32)

    @staticmethod
    def from_numpy(trajs: list[np.ndarray], max_points: int | None = None,
                   pad_trajs_to: int | None = None) -> "TrajectoryBatch":
        """Build a batch from a list of ``[n_i, 3]`` (x, y, t) arrays."""
        n = len(trajs)
        T = pad_trajs_to or n
        M = max_points or max((len(tr) for tr in trajs), default=1)
        x = np.zeros((T, M), np.float32)
        y = np.zeros((T, M), np.float32)
        t = np.zeros((T, M), np.float32)
        valid = np.zeros((T, M), bool)
        ids = np.full((T,), -1, np.int32)
        for i, tr in enumerate(trajs):
            tr = np.asarray(tr, np.float32)
            order = np.argsort(tr[:, 2], kind="stable")
            tr = tr[order][:M]
            m = len(tr)
            x[i, :m], y[i, :m], t[i, :m] = tr[:, 0], tr[:, 1], tr[:, 2]
            valid[i, :m] = True
            ids[i] = i
        return TrajectoryBatch(
            x=jnp.asarray(x), y=jnp.asarray(y), t=jnp.asarray(t),
            valid=jnp.asarray(valid), traj_id=jnp.asarray(ids))


@pytree_dataclass
class JoinResult:
    """Dense DTJ output (Problem 1), from the reference batch's perspective.

    ``best_w[r, m, c]``  : weight ``1 - d_s/eps_sp`` of the best match between
                           ref point ``(r, m)`` and candidate trajectory ``c``
                           (0 when no point of ``c`` is inside the cylinder).
    ``best_idx[r, m, c]``: point index (within the candidate row) of that best
                           match (-1 when none).
    After ``delta_t`` filtering, matches belonging to a common subsequence
    shorter than ``delta_t`` are zeroed (DTJ's Refine step).
    """

    best_w: jnp.ndarray    # [T, M, C] float32
    best_idx: jnp.ndarray  # [T, M, C] int32


@pytree_dataclass
class SubtrajSegmentation:
    """Output of TSA1/TSA2 (Problem 2) for a trajectory batch.

    ``cut[r, m]``     : True when point m starts a new subtrajectory
                        (cut[., 0] is always True for valid rows).
    ``sub_local[r,m]``: local subtrajectory index (0-based) of each point,
                        clipped to ``max_subtrajs_per_traj - 1``.
    ``num_subs[r]``   : number of subtrajectories of trajectory r.
    """

    cut: jnp.ndarray        # [T, M] bool
    sub_local: jnp.ndarray  # [T, M] int32
    num_subs: jnp.ndarray   # [T] int32
    score: jnp.ndarray      # [T, M] float32 — the window-difference signal d[]


@pytree_dataclass
class SubtrajTable:
    """The ST relation: one row per (traj, local subtraj) slot; S = T * maxS."""

    t_start: jnp.ndarray   # [S] float32
    t_end: jnp.ndarray     # [S] float32
    voting: jnp.ndarray    # [S] float32  (Eq. 6, mean point voting)
    card: jnp.ndarray      # [S] int32    (number of points)
    valid: jnp.ndarray     # [S] bool
    traj_row: jnp.ndarray  # [S] int32    (owning trajectory row)

    @property
    def num_slots(self) -> int:
        return self.t_start.shape[0]


@pytree_dataclass
class TopKSim:
    """Sparse SP relation: per-row top-K neighbor lists of the symmetrized,
    Eq. 2-normalized similarity matrix — the paper's adjacency lists,
    bounded to a static width ``K`` instead of densified to ``[S, S]``.

    Rows are sorted by similarity descending (``lax.top_k`` order: ties by
    ascending neighbor slot).  Entries beyond the row's positive degree
    carry ``ids == -1`` and ``sims == 0``.

    Exactness certificate: ``spill[s]`` is the (K+1)-th largest positive
    similarity of row ``s`` (0 when the row has at most K positive
    entries).  Every dropped entry of row ``s`` is ``<= spill[s]``, so
    whenever ``spill[s] < alpha`` the list provably contains *every*
    alpha-edge of ``s`` — and the clustering engines consuming this
    structure are label-identical to the dense oracle.  ``spill >= alpha``
    anywhere means K may have truncated a real alpha-edge: the overflow
    counter (``repro.core.similarity.topk_overflow``) is then nonzero and
    callers must widen K (``run_dsc`` auto-retries) or fail loudly.

    ``degree`` and the ``row_*`` moments are exact per-row statistics of
    the full (un-truncated) positive row — ``resolve_thresholds`` derives
    the same alpha/k from them as from the dense matrix.
    """

    ids: jnp.ndarray         # [S, K] int32 neighbor slot ids (-1 padding)
    sims: jnp.ndarray        # [S, K] float32, descending per row
    spill: jnp.ndarray       # [S] float32 (K+1)-th largest positive sim
    degree: jnp.ndarray      # [S] int32 positive entries of the full row
    row_sum: jnp.ndarray     # [S] float32 sum of positive entries
    row_sumsq: jnp.ndarray   # [S] float32 sum of squared positive entries

    @property
    def num_slots(self) -> int:
        return self.ids.shape[0]

    @property
    def k(self) -> int:
        return self.ids.shape[1]


@pytree_dataclass
class ClusteringResult:
    """Output of Algorithm 4 (+ Algorithm 5 refinement).

    States: ``member_of[s] == s`` and ``is_rep[s]``  -> representative;
            ``member_of[s] >= 0`` and not rep        -> cluster member;
            ``member_of[s] < 0``  (valid slot)       -> outlier.
    """

    member_of: jnp.ndarray   # [S] int32 (slot id of the cluster representative)
    member_sim: jnp.ndarray  # [S] float32 similarity to the representative
    is_rep: jnp.ndarray      # [S] bool
    is_outlier: jnp.ndarray  # [S] bool
    alpha_used: jnp.ndarray  # [] float32 — resolved absolute alpha
    k_used: jnp.ndarray      # [] float32 — resolved absolute k
