"""DPP-PMRF pipeline phases + legacy one-shot entry points.

The phase functions (``initialize``, ``optimize``) and result assembly
live here and are the substrate the session API (``repro.api``, DESIGN.md
§10) builds on.  The one-shot ``segment_image`` / ``segment_volume``
functions are **deprecated** shims over a module-level default session:
they still work (and now share compiled executables across calls), but new
code should drive ``repro.api.Segmenter`` directly for explicit
plan → compile → execute control and request batching.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import oversegment
from repro.core.pmrf import em as em_mod
from repro.core.pmrf.cliques import CliqueSet, enumerate_maximal_cliques
from repro.core.pmrf.energy import EnergyModel, make_energy_model
from repro.core.pmrf.graph import RegionGraph, build_region_graph
from repro.core.pmrf.hoods import (
    DEFAULT_CAPACITY_BUCKET,
    DEFAULT_SEGMENT_BUCKET,
    Hoods,
    build_hoods,
)


@dataclass
class Problem:
    """A fully-initialized PMRF problem (init phase output)."""

    graph: RegionGraph
    cliques: CliqueSet
    hoods: Hoods
    model: EnergyModel
    labels_px: np.ndarray  # (H, W) oversegmentation label map


@dataclass
class SegmentationResult:
    segmentation: np.ndarray      # (H, W) int32 {0..K-1}
    region_labels: np.ndarray     # (V,) int32
    mu: np.ndarray
    sigma: np.ndarray
    em_iters: int
    map_iters: int
    total_energy: float
    init_seconds: float
    optimize_seconds: float
    # Per-lane health (DESIGN.md §14): "converged" | "max_iters" |
    # "diverged" | "degenerate" | "running" (a lane read out mid-flight).
    status: str = "converged"

    @property
    def ok(self) -> bool:
        """True when the result is a legitimate segmentation."""
        return self.status in ("converged", "max_iters")


def initialize(
    image,
    *,
    overseg_grid: Tuple[int, int] = (16, 16),
    overseg_iters: int = 5,
    beta: float = 0.75,
    sigma_min: float = 2.0,
    n_labels: int = 2,
    oversegmentation=None,
    capacity_bucket: int = DEFAULT_CAPACITY_BUCKET,
    segment_bucket: int = DEFAULT_SEGMENT_BUCKET,
) -> Problem:
    """Initialization phase (paper Alg. 2 lines 1-5): graph + cliques +
    neighborhoods.  Untimed in the paper's methodology but fully built;
    each stage is a span (``plan.slic``, ``plan.graph``, ``plan.cliques``,
    ``plan.hoods``, ``plan.model``).
    ``n_labels`` sizes the model's label axis (K-ary segmentation,
    DESIGN.md §13); the graph/clique/hood structure is label-free.
    ``capacity_bucket`` / ``segment_bucket`` are the bucket grid on which
    the hood program compiles (one program per shape class, DESIGN.md §2);
    they do not change the result."""
    with obs.span("plan.slic"):
        img = jnp.asarray(image, jnp.float32)
        if oversegmentation is None:
            labels_px = oversegment.slic(img, grid=overseg_grid, iters=overseg_iters)
            n_regions = overseg_grid[0] * overseg_grid[1]
        else:
            labels_px = jnp.asarray(oversegmentation, jnp.int32)
            n_regions = int(np.asarray(labels_px).max()) + 1
        # The graph reads the label map on the host: the read (and so the
        # wait for SLIC on the device) belongs to this stage.
        labels_host = np.asarray(labels_px)
    with obs.span("plan.graph"):
        graph = build_region_graph(img, labels_px, n_regions)
    with obs.span("plan.cliques"):
        cliques = enumerate_maximal_cliques(graph)
    with obs.span("plan.hoods"):
        hoods = build_hoods(
            graph, cliques, capacity_bucket=capacity_bucket,
            segment_bucket=segment_bucket,
        )
    with obs.span("plan.model"):
        model = make_energy_model(
            graph.region_mean, graph.region_size, beta=beta, sigma_min=sigma_min,
            n_labels=n_labels,
        )
    return Problem(
        graph=graph,
        cliques=cliques,
        hoods=hoods,
        model=model,
        labels_px=labels_host,
    )


def _initial_params(problem: Problem, seed: int, init: str):
    n_labels = problem.model.n_labels  # K rides on the model (DESIGN.md §13)
    if init == "random":
        return em_mod.init_params(
            jax.random.PRNGKey(seed), problem.graph.n_regions, n_labels
        )
    return em_mod.quantile_init(
        problem.graph.region_mean, problem.graph.n_regions, n_labels
    )


def optimize(
    problem: Problem,
    *,
    seed: int = 0,
    config: em_mod.EMConfig = em_mod.EMConfig(),
    init: str = "random",
) -> em_mod.EMResult:
    """Optimization phase (the paper's timed region)."""
    labels0, mu0, sigma0 = _initial_params(problem, seed, init)
    return em_mod.run_em(
        problem.hoods, problem.model, labels0, mu0, sigma0, config
    )


def _legacy_session(
    overseg_grid, beta, mode, backend, init, max_em_iters, max_map_iters
):
    """Map the legacy kwarg pile onto an ExecutionConfig-keyed session."""
    from repro import api  # deferred: api builds on this module

    return api.session_for(
        api.ExecutionConfig(
            backend=backend,
            mode=mode,
            max_em_iters=max_em_iters,
            max_map_iters=max_map_iters,
            beta=beta,
            init=init,
            overseg_grid=tuple(overseg_grid),
        )
    )


def _warn_deprecated(name: str) -> None:
    warnings.warn(
        f"{name} is deprecated; use repro.api.Segmenter (plan/compile/execute"
        " + submit/drain, DESIGN.md §10). This shim routes through a shared"
        " default session and will be removed in a future release.",
        DeprecationWarning,
        stacklevel=3,
    )


def segment_image(
    image,
    *,
    seed: int = 0,
    overseg_grid: Tuple[int, int] = (16, 16),
    beta: float = 0.75,
    mode: str = "static",
    backend: str = "auto",
    init: str = "random",
    max_em_iters: int = 20,
    max_map_iters: int = 10,
    oversegmentation=None,
) -> SegmentationResult:
    """Deprecated one-shot entry point; see ``repro.api.Segmenter``."""
    _warn_deprecated("segment_image")
    sess = _legacy_session(
        overseg_grid, beta, mode, backend, init, max_em_iters, max_map_iters
    )
    plan = sess.plan(image, oversegmentation=oversegmentation)
    return sess.execute(plan, seed=seed)


def _assemble_result(
    problem: Problem,
    result: em_mod.EMResult,
    init_seconds: float,
    optimize_seconds: float,
) -> SegmentationResult:
    region_labels = np.asarray(result.labels)[: problem.graph.n_regions]
    seg = region_labels[problem.labels_px]
    return SegmentationResult(
        segmentation=seg.astype(np.int32),
        region_labels=region_labels,
        mu=np.asarray(result.mu),
        sigma=np.asarray(result.sigma),
        em_iters=int(result.em_iters),
        map_iters=int(result.map_iters),
        total_energy=float(result.total_energy),
        init_seconds=init_seconds,
        optimize_seconds=optimize_seconds,
        status=em_mod.STATUS_NAMES.get(int(result.status), "running"),
    )


def _can_batch(problems: List[Problem]) -> bool:
    """Batch when padding waste stays bounded: every slice's capacity within
    2x of the smallest (one bucket), so the shared trace doesn't burn the
    win on padding FLOPs.  Heterogeneous stacks fall back to the loop."""
    caps = [p.hoods.capacity for p in problems]
    return len(problems) > 1 and max(caps) <= 2 * min(caps)


def segment_volume(
    images,
    *,
    seed: int = 0,
    overseg_grid: Tuple[int, int] = (16, 16),
    beta: float = 0.75,
    mode: str = "static",
    backend: str = "auto",
    init: str = "random",
    max_em_iters: int = 20,
    max_map_iters: int = 10,
    batch: str = "auto",
) -> Tuple[List[SegmentationResult], float]:
    """Deprecated one-shot stack entry point; see ``Segmenter.segment_stack``.

    Returns (results, mean_optimize_seconds) — the paper reports the
    per-slice average of the optimization phase.  ``batch`` is one of
    ``"auto"`` (batch homogeneous stacks on accelerators; serial on CPU,
    where the warm-cache serial path is faster — see
    ``Segmenter.segment_stack``), ``"always"``, or ``"never"``; the batched
    path coalesces all slices into one vmapped launch through the
    session's executable cache, with per-slice results identical to the
    loop.
    """
    _warn_deprecated("segment_volume")
    sess = _legacy_session(
        overseg_grid, beta, mode, backend, init, max_em_iters, max_map_iters
    )
    return sess.segment_stack(images, seed=seed, batch=batch)
