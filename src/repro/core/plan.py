"""EnginePlan: the single tuned surface for every per-stage engine choice.

PRs 1-5 grew five per-stage engine switches (join mode, join kernel/index,
TSA2 kernel, clustering engine/kernel, similarity representation), each with
its own tile/block geometry, threaded separately through ``run_dsc``,
``build_dsc_program``, and the launcher CLI.  ``EnginePlan`` collapses that
surface to one frozen, hashable, JSON-serializable dataclass:

* every entry point accepts ``plan=`` (one object, one jit static key);
* the legacy flags survive as **deprecated aliases** that materialize a
  plan (``EnginePlan.from_legacy`` / ``resolve_plan``) — behavior is
  unchanged, so every pre-plan test and CI gate passes as-is;
* the autotuner (``repro.tune.autotune``) sweeps candidate plans and
  caches winners per (shape-bucket, backend, jax version); a stored plan
  round-trips through JSON (``save`` / ``load``).

Field-to-stage map (DESIGN.md §9; §§3-8 introduce each knob):

====================  =====================================================
stage                 plan fields
====================  =====================================================
join (Problem 1)      ``mode`` ("materialize" | "fused"), ``use_kernel``,
                      ``use_index``, fused tile geometry ``fused_rows`` /
                      ``fused_bc`` / ``fused_bm``
                      (``kernels.stjoin.ops.plan_fused_tiles``)
segmentation (P2)     ``seg_use_kernel`` (packed jnp engine vs the fused
                      Pallas Jaccard kernel — bit-identical cuts)
similarity (SP)       ``sim_mode`` ("dense" | "topk"), ``sim_topk`` (K),
                      ``sim_panel`` (Sb panel height); distributed-only:
                      ``sim_strategy``, ``sim_dtype``, ``sim_exchange``
                      ("allgather" barrier | "ring" streamed blocks)
comm (DESIGN.md §12)  ``halo_stream`` ("barrier" gathers every neighbor
                      slab up front | "ring" streams slabs and folds each
                      contribution as it lands), ``sim_exchange`` (above)
clustering (P3)       ``cluster_engine`` ("rounds" | "sequential"),
                      ``cluster_use_kernel``, round-kernel tiles
                      ``cluster_bu`` / ``cluster_bs``
====================  =====================================================

``None`` means "library default, resolved at run time" (e.g.
``fused_rows=None`` lets ``_fused_geometry`` pick the fat-tile default,
``sim_topk=None`` resolves to ``min(32, S)``).  Ints are concrete pins —
what the tuner writes once a sweep has measured a winner.
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
    mode: str = "materialize"          # "materialize" | "fused"
    use_kernel: bool = False           # Pallas join + SP sweep (materialize)
    use_index: bool = False            # grid candidate-tile pruning
    fused_rows: int | None = None      # fused ref-block rows (None = auto)
    fused_bc: int = 128                # fused candidate rows per block
    fused_bm: int = 128                # fused candidate point chunk
    # ---- segmentation (Problem 2) ----------------------------------------
    seg_use_kernel: bool = False       # Pallas TSA2 Jaccard kernel
    # ---- similarity (SP relation) ----------------------------------------
    sim_mode: str = "dense"            # "dense" | "topk"
    sim_topk: int | None = None        # K of the top-K lists (None = 32)
    sim_panel: int | None = None       # panel height Sb (None = 128-snap)
    sim_strategy: str = "psum"         # distributed dense collective shape
    sim_dtype: str = "f32"             # distributed dense payload dtype
    # ---- communication schedules (distributed-only) -----------------------
    halo_stream: str = "barrier"       # join halo slabs: "barrier" | "ring"
    sim_exchange: str = "allgather"    # similarity lists: "allgather" | "ring"
    # ---- clustering (Problem 3) ------------------------------------------
    cluster_engine: str = "rounds"     # "rounds" | "sequential"
    cluster_use_kernel: bool = False   # Pallas round-scan/claim-max kernels
    cluster_bu: int = 8                # row tile of the cluster kernels
    cluster_bs: int = 128              # column tile of the cluster kernels

    # ------------------------------------------------------------------ api
    def validate(self) -> "EnginePlan":
        """Raise ``ValueError`` on any inconsistent field; return ``self``.

        The error messages for the three engine selectors are the exact
        strings the pre-plan entry points raised, so existing error-path
        tests keep passing unchanged.
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
        overrides, which keeps jit cache keys (and therefore traces)
        identical to the pre-plan flag surface."""
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

    # ------------------------------------------------------- legacy aliases
    @classmethod
    def from_legacy(cls, *, mode: str = "materialize",
                    use_kernel: bool = False, use_index: bool = False,
                    fused_tiles: tuple | None = None,
                    seg_use_kernel: bool = False,
                    cluster_engine: str = "rounds",
                    cluster_use_kernel: bool = False,
                    sim_mode: str = "dense", sim_topk: int | None = None,
                    sim_panel: int | None = None,
                    sim_strategy: str = "psum",
                    sim_dtype: str = "f32",
                    halo_stream: str = "barrier",
                    sim_exchange: str = "allgather") -> "EnginePlan":
        """Materialize a plan from the deprecated per-stage flag set.

        This is the compatibility contract: every legacy flag combination
        maps onto exactly one plan, and running that plan is behaviorally
        identical to the pre-plan entry points (pinned by
        ``tests/test_plan.py``).
        """
        rows, bc, bm = (None, 128, 128) if fused_tiles is None else fused_tiles
        return cls(mode=mode, use_kernel=use_kernel, use_index=use_index,
                   fused_rows=rows, fused_bc=bc, fused_bm=bm,
                   seg_use_kernel=seg_use_kernel,
                   cluster_engine=cluster_engine,
                   cluster_use_kernel=cluster_use_kernel,
                   sim_mode=sim_mode, sim_topk=sim_topk, sim_panel=sim_panel,
                   sim_strategy=sim_strategy,
                   sim_dtype=sim_dtype, halo_stream=halo_stream,
                   sim_exchange=sim_exchange).validate()


_LEGACY_DEFAULTS = {
    "mode": "materialize", "use_kernel": False, "use_index": False,
    "fused_tiles": None, "seg_use_kernel": False,
    "cluster_engine": "rounds", "cluster_use_kernel": False,
    "sim_mode": "dense", "sim_topk": None, "sim_panel": None,
    "sim_strategy": "psum", "sim_dtype": "f32",
    "halo_stream": "barrier", "sim_exchange": "allgather",
}


def resolve_plan(plan: EnginePlan | None = None, **legacy) -> EnginePlan:
    """The one entry-point rule: a plan, or legacy flags — never both.

    ``plan=None`` materializes a plan from the legacy flags (all current
    callers).  With an explicit plan, any legacy flag still at a
    non-default value raises: silently preferring one surface over the
    other would make ``--plan`` + a stray ``--sim-mode`` ambiguous.
    """
    unknown = set(legacy) - set(_LEGACY_DEFAULTS)
    if unknown:
        raise TypeError(f"unknown legacy plan flags {sorted(unknown)}")
    if plan is None:
        return EnginePlan.from_legacy(**legacy)
    clash = {k: v for k, v in legacy.items()
             if v != _LEGACY_DEFAULTS[k] and v is not None}
    if clash:
        raise ValueError(
            f"both plan= and legacy per-stage flags were given ({clash}); "
            "the deprecated flags only exist to materialize a plan — "
            "set the fields on the plan instead")
    return plan.validate()
