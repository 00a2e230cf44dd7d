"""Execution policy for the session API (DESIGN.md §10).

``ExecutionConfig`` is the single place every execution knob lives.  Before
this existed, policy was smeared across ``EMConfig.mode``,
``EMConfig.backend``, the ``REPRO_KERNEL_BACKEND`` environment variable,
legacy ``use_pallas=`` kwargs, and per-call keyword arguments on
``segment_image`` — four half-overlapping surfaces with no defined
precedence.  The resolution order is now:

1. explicit ``ExecutionConfig`` field (``backend="auto"`` defers);
2. the ``REPRO_KERNEL_BACKEND`` environment variable;
3. the process-wide :func:`repro.kernels.ops.set_default_backend` override;
4. platform auto-detection (``pallas-tpu`` on TPU, else ``xla``).

Steps 2-4 are delegated to :func:`repro.kernels.ops.resolve_backend`, so
library code and the session API can never disagree.  Resolution happens
once, at ``Segmenter.compile`` time — the resolved name is baked into the
executable's cache key, so flipping the env var mid-session affects new
compilations only, never silently invalidates (or mismatches) cached ones.

The config is frozen and hashable: it doubles as the key for the
module-level session registry (one default ``Segmenter`` per distinct
config, see ``session.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Tuple

from repro.core.pmrf import em as em_mod
from repro.core.pmrf.hoods import DEFAULT_CAPACITY_BUCKET, DEFAULT_SEGMENT_BUCKET
from repro.kernels import ops as kops


@dataclass(frozen=True)
class FallbackPolicy:
    """Graceful degradation for compile/execute failures (DESIGN.md §14).

    When a compile or execute raises, the session first retries the same
    backend up to ``max_retries`` times with capped exponential backoff
    (transient-error cover: allocator pressure, interpreter hiccups),
    then — if ``enabled`` and the failing backend differs from
    ``backend`` — recompiles on the fallback backend.  Fallback
    executables get their own :class:`~repro.api.session.ExecutableKey`
    (the key pins the resolved backend), and the session remembers the
    redirect, so warm traffic routes straight to the fallback executable
    without re-attempting the broken compile.

    Frozen + hashable: rides on :class:`ExecutionConfig`, which keys the
    session registry and the executable cache.
    """

    enabled: bool = True
    backend: str = "xla"       # the universally-available lowering
    max_retries: int = 1       # same-backend retries before falling back
    backoff_s: float = 0.05    # initial backoff, doubled per retry
    max_backoff_s: float = 1.0

    def __post_init__(self):
        if self.backend not in kops.BACKENDS:
            raise ValueError(
                f"unknown fallback backend {self.backend!r}; have {kops.BACKENDS}"
            )
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.backoff_s < 0 or self.max_backoff_s < 0:
            raise ValueError("backoff seconds must be >= 0")


@dataclass(frozen=True)
class ExecutionConfig:
    """Every knob that selects *how* a segmentation problem executes.

    Problem-shaping knobs (oversegmentation grid, energy weights) live here
    too because they determine the planned problem's static shapes — two
    sessions with different grids produce different buckets and must not
    share executables.
    """

    # --- kernel / schedule selection -----------------------------------
    backend: str = "auto"   # auto | xla | pallas | pallas-tpu | pallas-interpret
    mode: str = "static"    # faithful | static | static-pallas

    # --- mixed precision (fused EM tick, DESIGN.md §16) ----------------
    # "f32" keeps every energy bit-identical to the golden oracle; "bf16"
    # runs the fused-tick energy arithmetic in bfloat16 with f32
    # accumulators (bounded-drift tolerance tier in the golden harness).
    # bf16 requires mode="static-pallas" — it is a property of the fused
    # kernel, not of the unfused compositions.  Part of `ExecutableKey`:
    # an f32 compile never aliases a bf16 one.
    precision: str = "f32"  # f32 | bf16

    # --- label space (K-ary multi-label segmentation, DESIGN.md §13) ----
    # n_labels sizes every label-indexed array the session plans/compiles
    # (model reseed quantiles, mu/sigma, tick pools) and widens the
    # compound key spaces by a factor of K.  It is part of
    # `ExecutableKey`, so a K=2 compile never aliases a K>2 one in the
    # LRU cache.  K=2 is the paper's binary PMRF, bit-identical to the
    # historical binary implementation.
    n_labels: int = 2

    # --- sharding (multi-device, DESIGN.md §11) ------------------------
    # shards > 1 block-partitions hood elements over `mesh_axis` of a
    # `shards`-device mesh and routes execution through the sharded
    # driver (`core.pmrf.distributed`).  Participates in backend
    # resolution indirectly (the same EMConfig is compiled per shard) and
    # in `ExecutableKey` directly: a sharded compile never aliases an
    # unsharded one.  Device availability is checked at compile time, not
    # here — on CPU, force virtual devices with
    # XLA_FLAGS=--xla_force_host_platform_device_count=N.
    shards: int = 1
    mesh_axis: str = "data"

    # --- optimization limits / convergence -----------------------------
    max_em_iters: int = 20
    max_map_iters: int = 10
    beta: float = 0.75
    sigma_min: float = 2.0
    init: str = "random"    # random | quantile

    # --- planning (oversegmentation) -----------------------------------
    overseg_grid: Tuple[int, int] = (16, 16)
    overseg_iters: int = 5

    # --- bucketing / caching -------------------------------------------
    # One grid for the EM executables' buckets and the plan's hood program,
    # which compiles once per shape class on it (DESIGN.md §2).
    capacity_bucket: int = DEFAULT_CAPACITY_BUCKET
    segment_bucket: int = DEFAULT_SEGMENT_BUCKET
    max_cached_executables: int = 32

    # --- fault tolerance (DESIGN.md §14) -------------------------------
    fallback: FallbackPolicy = FallbackPolicy()

    def __post_init__(self):
        if self.mode not in em_mod.MODES:
            raise ValueError(f"unknown mode {self.mode!r}; have {em_mod.MODES}")
        if self.precision not in em_mod.PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; have {em_mod.PRECISIONS}"
            )
        if self.precision == "bf16" and self.mode != "static-pallas":
            raise ValueError(
                "precision='bf16' requires mode='static-pallas' (the bf16 "
                "energy path lives in the fused EM-tick kernel)"
            )
        if self.init not in ("random", "quantile"):
            raise ValueError(f"init must be 'random' or 'quantile', got {self.init!r}")
        if self.backend not in (None, "auto", "pallas") and self.backend not in kops.BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; have "
                f"{('auto', 'pallas') + kops.BACKENDS}"
            )
        if self.n_labels < 2:
            raise ValueError(f"n_labels must be >= 2, got {self.n_labels}")
        if self.capacity_bucket < 1 or self.segment_bucket < 1:
            raise ValueError("bucket granularities must be >= 1")
        if self.max_cached_executables < 1:
            raise ValueError("max_cached_executables must be >= 1")
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if not self.mesh_axis or not isinstance(self.mesh_axis, str):
            raise ValueError(f"mesh_axis must be a non-empty string, got {self.mesh_axis!r}")
        if not isinstance(self.fallback, FallbackPolicy):
            raise ValueError(
                f"fallback must be a FallbackPolicy, got {type(self.fallback).__name__}"
            )
        # Tuples survive hashing; coerce list input once at construction.
        object.__setattr__(self, "overseg_grid", tuple(self.overseg_grid))

    def resolved_backend(self) -> str:
        """Concrete backend name after the full resolution order."""
        return kops.resolve_backend(self.backend)

    def em_config(self, backend: str | None = None) -> em_mod.EMConfig:
        """The inner-loop config, with the backend resolved *now* so the
        resulting trace is pinned to a concrete lowering (cache-key
        stability — see module docstring).  ``backend`` overrides the
        resolved name — the fallback-compile path (DESIGN.md §14) uses it
        to pin the fallback lowering."""
        return em_mod.EMConfig(
            max_em_iters=self.max_em_iters,
            max_map_iters=self.max_map_iters,
            mode=self.mode,
            beta=self.beta,
            sigma_min=self.sigma_min,
            backend=backend if backend is not None else self.resolved_backend(),
            precision=self.precision,
        )

    def with_(self, **changes) -> "ExecutionConfig":
        """Functional update (dataclasses.replace with validation)."""
        return replace(self, **changes)
