"""Data-parallel primitives (DPPs) — the paper's building-block vocabulary.

The paper (Lessley et al., DPP-PMRF) expresses the entire PMRF optimization
in eight canonical primitives: Map, Reduce, ReduceByKey, Scan, Scatter,
Gather, SortByKey, Unique.  This module is the TPU/JAX-native realization of
that vocabulary, used by both the PMRF engine (``repro.core.pmrf``) and the
LM stack (MoE dispatch, SSD scan, top-k sampling).

Two semantic adaptations vs. the VTK-m originals (see DESIGN.md §2):

* **Static shapes** — XLA requires static shapes, so compacting primitives
  (``unique``) return a padded array plus a ``count``; downstream consumers
  mask on ``count``.
* **Keyed reductions without sorting** — ``reduce_by_key`` takes explicit
  segment ids and a static ``num_segments`` (``jax.ops.segment_*``), because
  on TPU a scatter-reduce beats sort+adjacent-reduce when the key space is
  known.  ``sort_by_key`` is still provided (bitonic via ``lax.sort``) for
  the paper-faithful execution mode.

Inside :func:`profiled` every primitive runs eagerly to completion as a
``dpp.<Primitive>`` span (``repro.obs``), so the per-primitive breakdown of
the paper's §4.3.2 can be reproduced (``benchmarks/bench_fig4.py``).
"""

from __future__ import annotations

import contextlib
import math
import threading
from functools import partial, reduce
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

Array = jax.Array

# ---------------------------------------------------------------------------
# Profiling (per-DPP breakdown, paper §4.3.2)
# ---------------------------------------------------------------------------

_views = 0  # open profiled() views, changed under _views_lock
_views_lock = threading.Lock()


class DppProfile:
    """Per-primitive wall times: a view over the ``dpp.<Primitive>`` spans
    of one ``repro.obs`` recording (eager mode only).

    Inside ``jit`` the primitives fuse away; the view is intended for the
    benchmark harness, which runs the pipeline eagerly to reproduce the
    paper's per-DPP timing analysis.
    """

    def __init__(self, recorder: obs.Recorder):
        self._recorder = recorder

    @property
    def events(self) -> Dict[str, List[float]]:
        out: Dict[str, List[float]] = {}
        for r in self._recorder.records:
            if r.name.startswith("dpp.") and not math.isnan(r.end):
                out.setdefault(r.name[len("dpp."):], []).append(r.seconds)
        return out

    def totals(self) -> Dict[str, float]:
        return {k: float(sum(v)) for k, v in self.events.items()}

    def counts(self) -> Dict[str, int]:
        return {k: len(v) for k, v in self.events.items()}


@contextlib.contextmanager
def profiled():
    """Context manager enabling per-DPP timing; yields the profile."""
    global _views
    with obs.recording() as rec:
        with _views_lock:
            _views += 1
        try:
            yield DppProfile(rec)
        finally:
            with _views_lock:
                _views -= 1


def _timed(name: str, fn: Callable[[], Any]) -> Any:
    if not _views:
        return fn()
    # Eager timing: block on result so the measurement is honest.
    with obs.span(f"dpp.{name}"):
        out = fn()
        jax.block_until_ready(out)
    return out


# ---------------------------------------------------------------------------
# Canonical primitives
# ---------------------------------------------------------------------------


def map_(fn: Callable[..., Array], *arrays: Array) -> Array:
    """Map: apply ``fn`` elementwise over the input arrays (same shape)."""
    return _timed("Map", lambda: fn(*arrays))


def reduce_(values: Array, op: str = "add", initial: Optional[float] = None) -> Array:
    """Reduce: a single aggregate over all elements."""

    def run():
        if op == "add":
            return jnp.sum(values)
        if op == "min":
            return jnp.min(values) if initial is None else jnp.minimum(jnp.min(values), initial)
        if op == "max":
            return jnp.max(values) if initial is None else jnp.maximum(jnp.max(values), initial)
        raise ValueError(f"unknown reduce op: {op}")

    return _timed("Reduce", run)


#: Lanes per block of :func:`blocked_scan`.
SCAN_BLOCK = 1024


def blocked_scan(x: Array, op: str = "add") -> Array:
    """Inclusive scan (``op`` ``add`` or ``max``) of a 1-D integer or bool
    array, as scans within blocks of :data:`SCAN_BLOCK` lanes and a scan of
    the block totals, combined.  Integers combine exactly in any order, so
    the result is ``jnp.cumsum``'s (``lax.cummax``'s) bit for bit.

    The TPU compiler lowers one scan over n lanes as a window of n lanes,
    and its compile time grows much faster than n: on a described v5e about
    45 s at 750k lanes and over 15 minutes at the 2.6M lanes of a full
    1813x1830 slice's neighbourhood build.  Blocks keep every window at
    ``SCAN_BLOCK`` lanes.
    """
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    cum, combine, identity = {
        "add": (jnp.cumsum, jnp.add, 0),
        "max": (jax.lax.cummax, jnp.maximum, jnp.iinfo(x.dtype).min),
    }[op]
    n = x.shape[0]
    if n <= SCAN_BLOCK:
        return cum(x, axis=0)
    rows = -(-n // SCAN_BLOCK)
    blocks = jnp.pad(x, (0, rows * SCAN_BLOCK - n), constant_values=identity)
    inner = cum(blocks.reshape(rows, SCAN_BLOCK), axis=1)
    totals = blocked_scan(_row_last(inner), op)
    before = jnp.concatenate([jnp.full((1,), identity, x.dtype), totals[:-1]])
    return combine(inner, before[:, None]).reshape(-1)[:n]


def _row_last(x: Array) -> Array:
    """``x[:, -1]`` as a slice (indexing lowers to a gather under ``vmap``)."""
    return jax.lax.index_in_dim(x, x.shape[1] - 1, axis=1, keepdims=False)


def segmented_scan(
    values: Array, run_start: Array, op: str = "add", *, reverse: bool = False
) -> Array:
    """Inclusive segmented scan of a 1-D array over runs of lanes.

    Lane ``i`` begins a new run where ``run_start[i]`` is true; lane 0
    always does.  ``op="add"`` gives each lane the sum of its run up to
    and including itself; ``op="copy"`` gives it the value at its run's
    first lane.  ``reverse=True`` scans from the last lane over the same
    runs, so ``add`` sums to the run's end and ``copy`` gives every lane
    its run's last value.

    Blocked like :func:`blocked_scan`: a log-step scan within rows of
    :data:`SCAN_BLOCK` lanes, a segmented scan of the row carries, and one
    combine.  The order of summation depends only on the length and the
    flags, never on the platform's lowering, so a batched (``vmap``) scan
    gives every row the bits of the unbatched one.  Sums of integer-valued
    floats below 2**24 are exact in any order.
    """
    if op not in ("add", "copy"):
        raise ValueError(f"unknown segmented_scan op: {op}")

    def run():
        start = run_start.astype(bool)
        if not reverse:
            return _segmented_scan(values, start, op)
        # Read backwards, each run's last lane is its first.
        end = jnp.concatenate([start[1:], jnp.ones((1,), bool)])
        return _segmented_scan(values[::-1], end[::-1], op)[::-1]

    return _timed("SegmentedScan", run)


def _segmented_scan(values: Array, start: Array, op: str) -> Array:
    n = values.shape[0]
    start = start | (jnp.arange(n) == 0)
    if n <= SCAN_BLOCK:
        return _row_segmented_scan(start[None], values[None], op)[1][0]
    rows = -(-n // SCAN_BLOCK)
    pad = rows * SCAN_BLOCK - n
    flags = jnp.pad(start, (0, pad), constant_values=True)
    vals = jnp.pad(values, (0, pad))
    seen, inner = _row_segmented_scan(
        flags.reshape(rows, SCAN_BLOCK), vals.reshape(rows, SCAN_BLOCK), op
    )
    # Each row's last lane carries into the next row's lanes before that
    # row's first run start.
    carry = _segmented_scan(_row_last(inner), _row_last(seen), op)
    carry_in = jnp.concatenate([jnp.zeros((1,), values.dtype), carry[:-1]])[:, None]
    joined = carry_in + inner if op == "add" else jnp.broadcast_to(carry_in, inner.shape)
    return jnp.where(seen, inner, joined).reshape(-1)[:n]


def _row_segmented_scan(flags: Array, vals: Array, op: str) -> Tuple[Array, Array]:
    """Segmented inclusive scan along the last axis in log2(width) steps
    (Hillis-Steele): step ``d`` folds in the lane ``d`` to the left unless
    a run starts between.  Returns (a run start seen so far, the scan)."""
    width = vals.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)

    def shifted(x, d):
        return jnp.concatenate(
            [jnp.zeros(x.shape[:-1] + (d,), x.dtype), x[..., : width - d]], axis=-1
        )

    d = 1
    while d < width:
        has = lane >= d
        take = has & ~flags
        prev = shifted(vals, d)
        vals = jnp.where(take, prev + vals if op == "add" else prev, vals)
        flags = flags | (has & shifted(flags, d))
        d *= 2
    return flags, vals


def scan_(values: Array, *, exclusive: bool = False, axis: int = 0) -> Array:
    """Scan: prefix sum.  ``exclusive=True`` shifts by one (identity first).
    A 1-D integer scan runs as :func:`blocked_scan`; floats keep
    ``jnp.cumsum``'s order of summation."""

    def run():
        if values.ndim == 1 and not jnp.issubdtype(values.dtype, jnp.inexact):
            inc = blocked_scan(values)
        else:
            inc = jnp.cumsum(values, axis=axis)
        if not exclusive:
            return inc
        return inc - values

    return _timed("Scan", run)


def gather_(values: Array, indices: Array) -> Array:
    """Gather: ``out[i] = values[indices[i]]`` (leading axis)."""
    return _timed("Gather", lambda: jnp.take(values, indices, axis=0))


def scatter_(
    values: Array,
    indices: Array,
    out_size: int,
    *,
    mode: str = "set",
    fill: Any = 0,
    mask: Optional[Array] = None,
) -> Array:
    """Scatter: write ``values[i]`` to ``out[indices[i]]``.

    ``mode`` is one of ``set``/``add``/``min``/``max``.  Out-of-range indices
    are dropped (XLA semantics), which implements the masked-compaction idiom:
    pass ``mask`` to route invalid lanes to a dropped index.
    """

    def run():
        idx = indices
        if mask is not None:
            idx = jnp.where(mask, idx, out_size)  # out-of-range -> dropped
        shape = (out_size,) + values.shape[1:]
        base_val = jnp.asarray(fill, dtype=values.dtype)
        out = jnp.full(shape, base_val)
        ref = out.at[idx]
        if mode == "set":
            return ref.set(values, mode="drop")
        if mode == "add":
            return ref.add(values, mode="drop")
        if mode == "min":
            return ref.min(values, mode="drop")
        if mode == "max":
            return ref.max(values, mode="drop")
        raise ValueError(f"unknown scatter mode: {mode}")

    return _timed("Scatter", run)


def sort_by_key(
    keys: Array, *values: Array, num_keys: int = 1, stable: bool = True
) -> Tuple[Array, ...]:
    """SortByKey: stable ascending sort of ``keys`` carrying ``values``.

    ``keys`` may be a tuple of arrays (lexicographic, major first) by passing
    them stacked via ``compound_key`` or using ``num_keys > 1`` with keys as a
    2D ``(num_keys, n)`` array.  ``stable=False`` lets equal keys land in
    any order: where no values ride along, equal keys are indistinguishable,
    and the TPU compiler takes several times longer over a stable sort of
    more than one key.
    """

    def run():
        if num_keys == 1:
            operands = (keys,) + values
        else:
            operands = tuple(keys) + values
        return jax.lax.sort(operands, num_keys=num_keys, is_stable=stable)

    return _timed("SortByKey", run)


def compound_key(
    major: Array, minor: Array, minor_span: int, *, major_span: Optional[int] = None
) -> Array:
    """Pack (major, minor) int pairs into one sortable integer key.

    ``minor_span`` must be a static upper bound (exclusive) on ``minor``.
    Used for the paper's (cliqueId, vertexId) pair sorts.

    Overflow safety: a plain ``astype(jnp.int64)`` silently degrades to
    int32 when ``jax_enable_x64`` is off, corrupting keys for large
    (major, minor) spaces.  We pack in the widest *enabled* integer dtype
    and, when ``major_span`` (exclusive bound on ``major``) is supplied,
    statically verify the packed key space fits — raising instead of
    silently mis-sorting.  Callers with a key space beyond int32 and x64
    disabled should use ``sort_by_key(..., num_keys=2)`` (two-level
    lexicographic sort) instead.
    """
    dtype = jax.dtypes.canonicalize_dtype(jnp.int64)
    if major_span is not None:
        max_key = int(major_span) * int(minor_span) - 1
        if max_key > jnp.iinfo(dtype).max:
            raise OverflowError(
                f"compound_key space {major_span} x {minor_span} does not fit "
                f"{dtype.name}; enable jax_enable_x64 or use "
                "sort_by_key(num_keys=2) for a two-level sort"
            )
    return major.astype(dtype) * minor_span + minor.astype(dtype)


def reduce_by_key(
    segment_ids: Array,
    values: Array,
    num_segments: int,
    op: str = "add",
    *,
    indices_are_sorted: bool = False,
    backend: Optional[str] = None,
) -> Array:
    """ReduceByKey: segmented reduction to ``num_segments`` buckets.

    TPU-native form: callers supply segment ids directly (no sort required —
    see DESIGN.md §2).  For the paper-faithful path, first ``sort_by_key``
    then pass ``indices_are_sorted=True``.

    ``backend`` routes through the kernel dispatch layer (DESIGN.md §3):
    ``None`` keeps the XLA ``jax.ops.segment_*`` lowering; a pallas backend
    name (or ``"auto"``) dispatches to the MXU one-hot segment-reduce
    kernel for 1-D float values with ``op`` in {add, min}.
    """

    def run():
        if backend is not None:
            from repro.kernels import ops as kops  # lazy: keep dpp import light

            resolved = kops.resolve_backend(backend)
            if resolved != "xla":
                supported = (
                    op in ("add", "min")
                    and values.ndim == 1
                    and jnp.issubdtype(values.dtype, jnp.floating)
                )
                # Auto-routing guard: the one-hot kernel does O(S*N) work,
                # so segments~values-sized reductions (e.g. the faithful
                # mode's per-element min over capacity+1 segments) stay on
                # XLA regardless of the requested backend.
                if supported and num_segments <= kops.MAX_REDUCE_SEGMENTS:
                    return kops.segment_reduce(
                        values, segment_ids, num_segments, op, backend=resolved
                    )
                # Surface the downgrade (at trace time), whether the pallas
                # backend was named or auto-detected, so a run never
                # measures this reduction on XLA without saying so.
                import warnings

                reason = (
                    f"num_segments={num_segments} exceeds "
                    f"MAX_REDUCE_SEGMENTS={kops.MAX_REDUCE_SEGMENTS}"
                    if supported
                    else f"op={op!r}/dtype={values.dtype}/ndim={values.ndim}"
                    " unsupported by the pallas kernel"
                )
                warnings.warn(
                    f"reduce_by_key: {reason}; staying on XLA instead of "
                    f"{resolved!r}",
                    kops.BackendDowngradeWarning,
                    stacklevel=3,
                )
        kwargs = dict(
            num_segments=num_segments, indices_are_sorted=indices_are_sorted
        )
        if op == "add":
            return jax.ops.segment_sum(values, segment_ids, **kwargs)
        if op == "min":
            return jax.ops.segment_min(values, segment_ids, **kwargs)
        if op == "max":
            return jax.ops.segment_max(values, segment_ids, **kwargs)
        raise ValueError(f"unknown reduce_by_key op: {op}")

    return _timed("ReduceByKey", run)


def unique_(sorted_values, *, fill: Any = 0):
    """Unique: drop adjacent duplicates from a *sorted* array.

    Static-shape adaptation: returns ``(padded_uniques, count)`` where
    ``padded_uniques`` has the input length, the first ``count`` lanes hold
    the uniques (in order) and the remainder hold ``fill``.

    ``sorted_values`` may be a tuple of equal-length arrays sorted
    lexicographically (``sort_by_key(..., num_keys=n)``): a lane is then
    new when any of them differs from the lane before, the uniques come
    back as a tuple, and ``fill`` is a tuple of one fill per array.
    """

    def run():
        multi = isinstance(sorted_values, tuple)
        cols = sorted_values if multi else (sorted_values,)
        fills = fill if multi else (fill,)
        n = cols[0].shape[0]
        differs = reduce(
            jnp.logical_or, [c[1:] != c[:-1] for c in cols]
        )
        is_new = jnp.concatenate([jnp.ones((1,), dtype=bool), differs])
        # Exclusive scan of the "new element" flags gives the write position.
        pos = scan_(is_new.astype(jnp.int32), exclusive=True)
        out = tuple(
            scatter_(c, pos, n, mode="set", fill=f, mask=is_new)
            for c, f in zip(cols, fills)
        )
        count = jnp.sum(is_new.astype(jnp.int32))
        return (out if multi else out[0]), count

    return _timed("Unique", run)


# ---------------------------------------------------------------------------
# Composite DPP idioms used throughout the paper's pipeline
# ---------------------------------------------------------------------------


def counts_to_offsets(counts: Array) -> Array:
    """CSR offsets from per-row counts: ``offsets[i] = sum(counts[:i])``.

    Returns length ``n+1`` (last entry = total).  Built from Scan.
    """
    total = jnp.sum(counts)
    excl = scan_(counts, exclusive=True)
    return jnp.concatenate([excl, total[None]]).astype(jnp.int32)


def expand(counts: Array, total: int) -> Array:
    """The DPP "expand"/replicate idiom (paper's repHoods construction).

    Given per-row ``counts`` and the static padded output length ``total``,
    returns ``src`` of shape ``(total,)`` with ``src[j] = i`` for the j-th
    output lane belonging to row i.  Lanes beyond ``sum(counts)`` map to the
    last row+1... they are filled with ``len(counts)`` (an out-of-range
    sentinel) so callers can mask.  Built from Scatter + Scan (max-scan).
    """
    n = counts.shape[0]
    offsets = scan_(counts, exclusive=True).astype(jnp.int32)
    valid = counts > 0
    # Scatter row ids at their start offsets, then a running max fills gaps.
    marks = scatter_(
        jnp.arange(n, dtype=jnp.int32),
        offsets,
        total,
        mode="max",
        fill=-1,
        mask=valid,
    )
    src = blocked_scan(marks, "max")
    nvalid = jnp.sum(counts).astype(jnp.int32)
    lane = jnp.arange(total, dtype=jnp.int32)
    return jnp.where(lane < nvalid, src, n).astype(jnp.int32)


def expand_with_rank(counts: Array, total: int) -> Tuple[Array, Array]:
    """Like :func:`expand` but also returns the within-row rank of each lane."""
    src = expand(counts, total)
    n = counts.shape[0]
    offsets = scan_(counts, exclusive=True).astype(jnp.int32)
    safe_src = jnp.minimum(src, n - 1)
    rank = jnp.arange(total, dtype=jnp.int32) - jnp.take(offsets, safe_src)
    return src, jnp.where(src < n, rank, 0)


def segments_from_sorted(sorted_keys: Array) -> Array:
    """Dense segment ids (0..k-1) from a sorted key array (Scan over flags)."""
    first = jnp.zeros((1,), dtype=jnp.int32)
    is_new = jnp.concatenate(
        [first, (sorted_keys[1:] != sorted_keys[:-1]).astype(jnp.int32)]
    )
    return scan_(is_new)


def select_flagged(values: Array, flags: Array, *, fill: Any = 0) -> Tuple[Array, Array]:
    """Stream-compaction: stable-pack lanes where ``flags`` is true.

    Returns ``(packed, count)`` with static length (= input length).
    Scan + Scatter, the canonical DPP compaction.
    """
    flags_i = flags.astype(jnp.int32)
    pos = scan_(flags_i, exclusive=True)
    n = values.shape[0]
    packed = scatter_(values, pos, n, mode="set", fill=fill, mask=flags)
    return packed, jnp.sum(flags_i)


__all__ = [
    "DppProfile",
    "profiled",
    "map_",
    "reduce_",
    "scan_",
    "gather_",
    "scatter_",
    "sort_by_key",
    "compound_key",
    "reduce_by_key",
    "unique_",
    "blocked_scan",
    "segmented_scan",
    "counts_to_offsets",
    "expand",
    "expand_with_rank",
    "segments_from_sorted",
    "select_flagged",
]
