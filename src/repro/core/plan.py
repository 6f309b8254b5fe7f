"""EnginePlan: the one configuration surface for every per-stage engine
and tile choice of the DSC pipeline.

Every entry point — ``run_dsc``, ``run_dsc_lowerable``,
``run_dsc_distributed``, ``build_dsc_program``, the resilient runners and
the launcher's ``--plan PLAN.json`` — takes one frozen, hashable,
JSON-serializable :class:`EnginePlan` (``None`` = ``EnginePlan()``).  A
plan rides through ``jax.jit`` as one static argument: one plan == one
trace.  The autotuner (``repro.tune.autotune``) sweeps candidate plans and
caches winners per (shape-bucket, backend, jax version); a stored plan
round-trips through JSON (``save`` / ``load``).  DESIGN.md §9 has the
design; §§3-8 and §12 introduce each field.

What each field selects, by stage (the oracle of each fork is named; every
fork is label-identical to its oracle):

join (Problem 1)
    ``mode="materialize"`` (the parity oracle) builds the DTJ join cube
    ``JoinResult [T, M, C]`` in HBM and re-reads it for voting, the TSA2
    masks and the similarity scatter.  ``mode="fused"`` streams the join
    through two Pallas sweeps that accumulate those outputs directly; the
    cube never exists (``DSCOutput.join is None``) and pass 2 re-sweeps
    after segmentation.  On a mesh, fused mode streams each halo slab and
    all_gathers the TSA2 neighbor sets as packed words.  ``use_kernel``
    runs the materialize-mode join and the similarity scatter through the
    Pallas kernels (the jnp join is the oracle).  ``use_index`` prunes
    candidate tiles with the spatiotemporal grid (``repro.index.grid``)
    in every mode; pruning is conservative, so outputs are unchanged.
    Index planning is host-driven, so it needs concrete inputs (not
    ``run_dsc_lowerable``).  On a mesh it also exchanges eps-expanded
    partition bboxes and masks each halo slab down to the points the
    neighbor can match.  ``fused_rows`` / ``fused_bc`` / ``fused_bm``
    pin the fused sweeps' tile geometry
    (``kernels.stjoin.ops.plan_fused_tiles``).
segmentation (Problem 2)
    ``seg_use_kernel`` computes the TSA2 Jaccard signal with the fused
    Pallas kernel (``repro.kernels.jaccard``) instead of the jnp
    packed-word engine (the oracle) — bit-identical cuts; a no-op under
    ``tsa1``.
similarity (SP relation)
    ``sim_mode="dense"`` (the oracle) builds the ``[S, S]`` SP matrix.
    ``sim_mode="topk"`` streams ``[S, K]`` neighbor lists in panels of
    ``sim_panel`` rows (DESIGN.md §8): O(S*K + Sb*S) memory, labels
    bit-identical to dense whenever the spill certificate holds
    (``sim_overflow == 0``; ``run_dsc``'s ``on_overflow`` names what
    happens otherwise).  ``sim_topk`` is K.  On a mesh each model rank
    builds its ``[S, S/m]`` column block, an all_to_all hands it the
    transpose-partner rows, and only per-rank top-(K+1) lists are
    gathered.  ``sim_strategy`` shapes the distributed dense collective:
    ``"psum"`` all-reduces the ``[S, S]`` matrix, ``"allgather"`` gathers
    each rank's column block; ``sim_dtype="bf16"`` halves its payload.
    Both are ignored under ``topk``.
communication (distributed only, DESIGN.md §12)
    ``halo_stream="barrier"`` gathers every neighbor slab up front;
    ``"ring"`` streams slabs around a ``ppermute`` ring and folds each
    contribution while the next is in flight (fused mode rings only its
    word/mask gathers).  ``sim_exchange="ring"`` turns the top-K list
    gather (and the dense ``allgather`` strategy's block gather) into a
    forwarding ring with a running merge; the ``psum`` strategy ignores
    it.  Every ring is bit-identical to its barrier twin.
clustering (Problem 3)
    ``cluster_engine="rounds"`` is the round-parallel greedy engine;
    ``"sequential"`` is the O(S) oracle (DESIGN.md §6).
    ``cluster_use_kernel`` backs the round engine with the Pallas tile
    kernels (``repro.kernels.cluster``) of tile ``cluster_bu`` x
    ``cluster_bs``; the jnp formulation is faster on CPU, where the
    kernels run in interpret mode.

``None`` means "library default, resolved at run time" (``fused_rows=None``
lets ``_fused_geometry`` pick the fat-tile default, ``sim_topk=None``
resolves to ``min(32, S)``).  Ints are concrete pins — what the tuner
writes once a sweep has measured a winner.
"""
from __future__ import annotations

import dataclasses
import json

_MODES = ("materialize", "fused")
_ENGINES = ("rounds", "sequential")
_SIM_MODES = ("dense", "topk")
_SIM_STRATEGIES = ("psum", "allgather")
_SIM_DTYPES = ("f32", "bf16")
_HALO_STREAMS = ("barrier", "ring")
_SIM_EXCHANGES = ("allgather", "ring")


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """One per-stage engine/tile configuration for the whole DSC pipeline.

    Frozen and hashable so a plan can ride directly through ``jax.jit``
    static arguments: one plan == one trace (the one-trace-per-geometry
    invariant the autotuner relies on).
    """

    # ---- join (Problem 1) -------------------------------------------------
    mode: str = "materialize"          # "materialize" (oracle) | "fused"
    use_kernel: bool = False           # Pallas join + SP scatter (materialize)
    use_index: bool = False            # grid candidate-tile pruning
    fused_rows: int | None = None      # fused ref-block rows (None = auto)
    fused_bc: int = 128                # fused candidate rows per block
    fused_bm: int = 128                # fused candidate point chunk
    # ---- segmentation (Problem 2) ----------------------------------------
    seg_use_kernel: bool = False       # Pallas TSA2 Jaccard kernel
    # ---- similarity (SP relation) ----------------------------------------
    sim_mode: str = "dense"            # "dense" (oracle) | "topk"
    sim_topk: int | None = None        # K of the top-K lists (None = 32)
    sim_panel: int | None = None       # panel height Sb (None = 128-snap)
    sim_strategy: str = "psum"         # mesh dense collective: "psum" |
                                       # "allgather" (column blocks)
    sim_dtype: str = "f32"             # mesh dense payload: "f32" | "bf16"
    # ---- communication schedules (distributed-only) -----------------------
    halo_stream: str = "barrier"       # join halo slabs: "barrier" | "ring"
    sim_exchange: str = "allgather"    # similarity lists: "allgather" | "ring"
    # ---- clustering (Problem 3) ------------------------------------------
    cluster_engine: str = "rounds"     # "rounds" | "sequential" (oracle)
    cluster_use_kernel: bool = False   # Pallas round-scan/claim-max kernels
    cluster_bu: int = 8                # row tile of the cluster kernels
    cluster_bs: int = 128              # column tile of the cluster kernels

    # ------------------------------------------------------------------ api
    def validate(self) -> "EnginePlan":
        """Raise ``ValueError`` on any inconsistent field; return ``self``.

        A plan loaded from JSON is checked here too, so a malformed file
        fails with the field's name before anything compiles.
        """
        if self.mode not in _MODES:
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.cluster_engine not in _ENGINES:
            raise ValueError(f"unknown cluster engine {self.cluster_engine!r}")
        if self.sim_mode not in _SIM_MODES:
            raise ValueError(f"unknown sim_mode {self.sim_mode!r}")
        if self.sim_strategy not in _SIM_STRATEGIES:
            raise ValueError(f"unknown sim_strategy {self.sim_strategy!r}")
        if self.sim_dtype not in _SIM_DTYPES:
            raise ValueError(f"unknown sim_dtype {self.sim_dtype!r}")
        if self.halo_stream not in _HALO_STREAMS:
            raise ValueError(f"unknown halo_stream {self.halo_stream!r}")
        if self.sim_exchange not in _SIM_EXCHANGES:
            raise ValueError(f"unknown sim_exchange {self.sim_exchange!r}")
        for name in ("fused_rows", "sim_topk", "sim_panel"):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{name} must be None or a positive int, "
                                 f"got {v!r}")
        for name in ("fused_bc", "fused_bm", "cluster_bu", "cluster_bs"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        return self

    def replace(self, **kw) -> "EnginePlan":
        """A copy with fields replaced (validated)."""
        return dataclasses.replace(self, **kw).validate()

    @property
    def fused_tiles(self) -> tuple[int | None, int, int] | None:
        """``(rows, bc, bm)`` fused-kernel geometry, or ``None`` when every
        fused field still holds the library default — callers then pass no
        overrides, so the kernels' own defaults pick the geometry."""
        t = (self.fused_rows, self.fused_bc, self.fused_bm)
        return None if t == (None, 128, 128) else t

    @property
    def cluster_tiles(self) -> tuple[int, int]:
        """``(bu, bs)`` tile geometry of the Pallas clustering kernels
        (``kernels.cluster.ops``); the list-tile kernels use ``bu`` as
        their row tile."""
        return (self.cluster_bu, self.cluster_bs)

    # --------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "EnginePlan":
        """Strict inverse of ``to_dict``: unknown keys raise (a stored plan
        from a future schema must fail loudly, not silently drop fields);
        missing keys take the field default."""
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(
                f"unknown EnginePlan fields {sorted(unknown)}; "
                f"known fields: {sorted(names)}")
        return cls(**d).validate()

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "EnginePlan":
        return cls.from_dict(json.loads(s))

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path) -> "EnginePlan":
        with open(path) as f:
            return cls.from_json(f.read())
