"""Kernel-dispatch execution layer (DESIGN.md §3).

Every accelerated op in the repo resolves through a registry keyed by
``(op, backend)`` with three built-in backends:

* ``pallas-tpu``       — compiled Pallas kernels (TPU target)
* ``pallas-interpret`` — the same kernels through the Pallas interpreter
                         (any backend; slow — for validation and parity
                         testing, never production CPU use)
* ``xla``              — pure-jnp reference implementations
                         (``kernels/ref.py``), XLA's own fusion

Backend resolution order, per call:

1. explicit ``backend=`` argument (``"auto"``/``None`` defer);
2. the ``REPRO_KERNEL_BACKEND`` environment variable;
3. a process-wide :func:`set_default_backend` override;
4. auto-detection: ``pallas-tpu`` iff ``jax.default_backend() == "tpu"``,
   else ``xla``.

Library code calls the wrappers below, never the kernels directly; new
lowerings plug in via :func:`register` without touching call sites.  (The
pre-registry ``use_pallas=`` boolean went through its one-release
deprecation window and has been removed; pass ``backend=`` or configure
``repro.api.ExecutionConfig(backend=...)``.)

A size guard that sends work resolved to a Pallas backend to the XLA
composition instead always says so with a :class:`BackendDowngradeWarning`
(at trace time), whether the backend was named or auto-detected, so a run
on the chip cannot silently measure the wrong implementation.
"""

from __future__ import annotations

import os
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.kernels import ref
from repro.kernels.em_tick import fused_em_tick_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.map_step import BLOCK as MAP_STEP_BLOCK
from repro.kernels.map_step import SEG_ALIGN, fused_map_step_pallas
from repro.kernels.mrf_energy import mrf_min_energy_pallas
from repro.kernels.segment_reduce import segment_reduce_pallas

Array = jax.Array

BACKENDS = ("pallas-tpu", "pallas-interpret", "xla")

ENV_VAR = "REPRO_KERNEL_BACKEND"

# The fused map-step kernel holds BOTH one-hot tiles (hood and vertex,
# each (roundup(segments,128) x 1024) f32) in VMEM at once; bound their
# combined footprint well under the ~16 MB/core so inputs/outputs fit too.
# Beyond this the dispatch falls back to the reference composition.
MAX_ONEHOT_BYTES = 8 * 1024 * 1024

# One-hot segment reduction is O(num_segments * num_values) compute vs the
# O(num_values) XLA scatter; it only wins while the segment axis is small
# enough to amortize on the MXU.  Auto-routing (dpp.reduce_by_key) keeps
# reductions with more segments than this on the XLA path.
MAX_REDUCE_SEGMENTS = 4096

_default_override: Optional[str] = None


class BackendDowngradeWarning(UserWarning):
    """Work resolved to a Pallas backend ran on the XLA composition because
    a size guard refused the kernel."""


class FusedKernelDowngradeWarning(BackendDowngradeWarning):
    """The fused EM-tick / MAP-step kernels' VMEM guard refused the
    kernel (:func:`_onehot_guard`)."""


_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def register(op: str, backend: str) -> Callable[[Callable], Callable]:
    """Register an implementation for ``(op, backend)`` in the dispatch table."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, backend)] = fn
        return fn

    return deco


def registered_ops() -> Tuple[str, ...]:
    return tuple(sorted({op for op, _ in _REGISTRY}))


def set_default_backend(backend: Optional[str]) -> None:
    """Process-wide backend override (below the env var, above auto-detect).

    Pass ``None`` to restore auto-detection.
    """
    global _default_override
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    _default_override = backend


def _auto_backend() -> str:
    return "pallas-tpu" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve ``backend`` (possibly ``None``/``"auto"``) to a concrete name."""
    if backend in (None, "auto"):
        backend = os.environ.get(ENV_VAR) or _default_override or _auto_backend()
    if backend == "pallas":  # platform-appropriate pallas flavour
        backend = "pallas-tpu" if jax.default_backend() == "tpu" else "pallas-interpret"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    return backend


def _dispatch(op: str, backend: str) -> Callable:
    try:
        return _REGISTRY[(op, backend)]
    except KeyError:
        raise NotImplementedError(f"op {op!r} has no {backend!r} implementation")


# ---------------------------------------------------------------------------
# segment_reduce
# ---------------------------------------------------------------------------

register("segment_reduce", "xla")(ref.segment_reduce)


@register("segment_reduce", "pallas-tpu")
def _segment_reduce_tpu(values, segment_ids, num_segments, op):
    return segment_reduce_pallas(values, segment_ids, num_segments, op, interpret=False)


@register("segment_reduce", "pallas-interpret")
def _segment_reduce_interp(values, segment_ids, num_segments, op):
    return segment_reduce_pallas(values, segment_ids, num_segments, op, interpret=True)


def segment_reduce(
    values: Array,
    segment_ids: Array,
    num_segments: int,
    op: str = "add",
    *,
    backend: Optional[str] = None,
) -> Array:
    backend = resolve_backend(backend)
    return _dispatch("segment_reduce", backend)(values, segment_ids, num_segments, op)


# ---------------------------------------------------------------------------
# mrf_min_energy
# ---------------------------------------------------------------------------

register("mrf_min_energy", "xla")(ref.mrf_min_energy)


@register("mrf_min_energy", "pallas-tpu")
def _mrf_min_energy_tpu(y, w, n1_e, nall_e, xf, mu, sigma, beta):
    return mrf_min_energy_pallas(y, w, n1_e, nall_e, xf, mu, sigma, beta, interpret=False)


@register("mrf_min_energy", "pallas-interpret")
def _mrf_min_energy_interp(y, w, n1_e, nall_e, xf, mu, sigma, beta):
    return mrf_min_energy_pallas(y, w, n1_e, nall_e, xf, mu, sigma, beta, interpret=True)


def mrf_min_energy(
    y: Array,
    w: Array,
    n1_e: Array,
    nall_e: Array,
    xf: Array,
    mu: Array,
    sigma: Array,
    beta,
    *,
    backend: Optional[str] = None,
) -> Tuple[Array, Array]:
    backend = resolve_backend(backend)
    return _dispatch("mrf_min_energy", backend)(y, w, n1_e, nall_e, xf, mu, sigma, beta)


# ---------------------------------------------------------------------------
# fused_map_step — the whole static-mode MAP iteration body in one launch
# ---------------------------------------------------------------------------


def _onehot_guard(op: str, backend: str, n_hoods: int, n_vertices: int) -> str:
    """The fused kernels' VMEM guard: the backend to run ``op`` on.

    Both fused kernels hold the hood and vertex one-hot tiles in VMEM at
    once; past :data:`MAX_ONEHOT_BYTES` the op runs on the reference
    composition (which still avoids the per-iteration sort and keeps the
    hoisted reductions) and a :class:`FusedKernelDowngradeWarning` says so.
    """
    if backend == "xla":
        return backend
    pad = lambda s: -(-s // SEG_ALIGN) * SEG_ALIGN
    onehot_bytes = (pad(n_hoods) + pad(n_vertices)) * MAP_STEP_BLOCK * 4
    if onehot_bytes <= MAX_ONEHOT_BYTES:
        return backend
    warnings.warn(
        f"{op}: one-hot tiles for (n_hoods={n_hoods}, "
        f"n_vertices={n_vertices}) need {onehot_bytes/2**20:.1f} "
        f"MB VMEM (> {MAX_ONEHOT_BYTES/2**20:.0f} MB); falling "
        f"back from {backend!r} to the 'xla' composition",
        FusedKernelDowngradeWarning,
        stacklevel=3,
    )
    return "xla"


register("fused_map_step", "xla")(ref.fused_map_step)


@register("fused_map_step", "pallas-tpu")
def _fused_map_step_tpu(y, w, cnt_e, nall_e, xf, valid, hood_id, vertex, mu, sigma, beta, *, n_hoods, n_vertices):
    return fused_map_step_pallas(
        y, w, cnt_e, nall_e, xf, valid, hood_id, vertex, mu, sigma, beta,
        n_hoods=n_hoods, n_vertices=n_vertices, interpret=False,
    )


@register("fused_map_step", "pallas-interpret")
def _fused_map_step_interp(y, w, cnt_e, nall_e, xf, valid, hood_id, vertex, mu, sigma, beta, *, n_hoods, n_vertices):
    return fused_map_step_pallas(
        y, w, cnt_e, nall_e, xf, valid, hood_id, vertex, mu, sigma, beta,
        n_hoods=n_hoods, n_vertices=n_vertices, interpret=True,
    )


def fused_map_step(
    y: Array,
    w: Array,
    cnt_e: Array,
    nall_e: Array,
    xf: Array,
    valid: Array,
    hood_id: Array,
    vertex: Array,
    mu: Array,
    sigma: Array,
    beta,
    *,
    n_hoods: int,
    n_vertices: int,
    backend: Optional[str] = None,
) -> Tuple[Array, Array, Array, Array]:
    """Fused K-ary MAP step: (min_e, arg, hood_energy_sums, votes).

    ``cnt_e`` is (K, H) — each label's per-element neighborhood count —
    and ``mu``/``sigma`` are (K,); ``votes`` comes back (K, n_vertices)
    (DESIGN.md §13).
    """
    backend = _onehot_guard(
        "fused_map_step", resolve_backend(backend), n_hoods, n_vertices
    )
    return _dispatch("fused_map_step", backend)(
        y, w, cnt_e, nall_e, xf, valid, hood_id, vertex, mu, sigma, beta,
        n_hoods=n_hoods, n_vertices=n_vertices,
    )


# ---------------------------------------------------------------------------
# fused_em_tick — the whole EM tick (counts + MAP + M-step + convergence)
# in one launch (DESIGN.md §16)
# ---------------------------------------------------------------------------

register("fused_em_tick", "xla")(ref.fused_em_tick)


@register("fused_em_tick", "pallas-tpu")
def _fused_em_tick_tpu(y, w, nall_e, xf, valid, hood_id, vertex, region_mean,
                       region_weight, hist, mu, sigma, beta, *,
                       n_hoods, n_vertices, precision, conv_tol):
    return fused_em_tick_pallas(
        y, w, nall_e, xf, valid, hood_id, vertex, region_mean, region_weight,
        hist, mu, sigma, beta, n_hoods=n_hoods, n_vertices=n_vertices,
        precision=precision, conv_tol=conv_tol, interpret=False,
    )


@register("fused_em_tick", "pallas-interpret")
def _fused_em_tick_interp(y, w, nall_e, xf, valid, hood_id, vertex, region_mean,
                          region_weight, hist, mu, sigma, beta, *,
                          n_hoods, n_vertices, precision, conv_tol):
    return fused_em_tick_pallas(
        y, w, nall_e, xf, valid, hood_id, vertex, region_mean, region_weight,
        hist, mu, sigma, beta, n_hoods=n_hoods, n_vertices=n_vertices,
        precision=precision, conv_tol=conv_tol, interpret=True,
    )


def fused_em_tick(
    y: Array,
    w: Array,
    nall_e: Array,
    xf: Array,
    valid: Array,
    hood_id: Array,
    vertex: Array,
    region_mean: Array,
    region_weight: Array,
    hist: Array,
    mu: Array,
    sigma: Array,
    beta,
    *,
    n_hoods: int,
    n_vertices: int,
    precision: str = "f32",
    conv_tol: float = 1.0e-4,
    backend: Optional[str] = None,
) -> Tuple[Array, ...]:
    """Fused EM tick: one launch for counts + MAP iterate + M-step +
    convergence.  Returns ``(labels, hood_e, votes, conv, sum_w, sum_wy,
    sum_wyy)`` (DESIGN.md §16).

    Shares ``fused_map_step``'s VMEM guard (:func:`_onehot_guard`):
    oversized segment spaces run the reference composition (which still
    fuses at the XLA level — no per-tick sort, one trace).
    """
    backend = _onehot_guard(
        "fused_em_tick", resolve_backend(backend), n_hoods, n_vertices
    )
    return _dispatch("fused_em_tick", backend)(
        y, w, nall_e, xf, valid, hood_id, vertex, region_mean, region_weight,
        hist, mu, sigma, beta, n_hoods=n_hoods, n_vertices=n_vertices,
        precision=precision, conv_tol=conv_tol,
    )


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@register("flash_attention", "xla")
def _flash_attention_xla(q, k, v, *, causal, scale, block_q, block_k):
    del block_q, block_k
    return ref.flash_attention(q, k, v, causal=causal, scale=scale)


@register("flash_attention", "pallas-tpu")
def _flash_attention_tpu(q, k, v, *, causal, scale, block_q, block_k):
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=False,
    )


@register("flash_attention", "pallas-interpret")
def _flash_attention_interp(q, k, v, *, causal, scale, block_q, block_k):
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=True,
    )


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> Array:
    backend = resolve_backend(backend)
    return _dispatch("flash_attention", backend)(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k
    )
