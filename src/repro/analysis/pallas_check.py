"""Pallas kernel checker: static BlockSpec/grid verification (DESIGN.md §15).

Operates on the `pallas_call` eqns found inside a traced jaxpr — the
same representation the compiler sees, so the checks hold for every
call site that routes through ``kernels/ops.py`` regardless of which
wrapper produced the launch.

The race model (PL101/PL104): a grid axis is *revisited* by an output
when the output's BlockSpec index map does not depend on that axis —
the same output block is then written at every point along it, and the
kernel body typically accumulates (``o_ref[...] += ...``).  That is
well-defined only if the axis executes sequentially.  Mosaic's
``dimension_semantics`` declares this per axis: ``"arbitrary"`` pins
sequential-in-order execution, ``"parallel"`` licenses the compiler to
parallelize.  A revisited axis declared ``parallel`` is a write-write
race (PL101, error); a revisited axis with NO declaration is safe only
by TPU Mosaic's implicit sequential default and races the moment the
kernel is retargeted at a parallel-grid backend (PL104, warning) —
this is the race class that bit the K-grid rewrite, now machine-checked.

Bounds (PL102) and divisibility (PL103) are evaluated by concretely
executing each BlockSpec's index-map jaxpr at sampled grid corners —
the maps in this codebase are affine, so corner sampling is exact.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as pl

from .findings import Finding
from .jaxpr_lint import iter_eqns

__all__ = ["KernelReport", "find_pallas_calls", "check_pallas_call", "check_jaxpr_kernels"]

#: Cap on sampled grid points per index map (3 samples/axis, exact for
#: the affine maps BlockSpecs are in practice).
_AXIS_SAMPLES = 3


@dataclass
class KernelReport:
    """Static census for one pallas_call (recorded in ANALYSIS.json)."""

    name: str
    grid: Tuple[int, ...]
    dimension_semantics: Optional[Tuple[str, ...]]
    revisited_axes: Dict[str, List[int]] = field(default_factory=dict)
    findings: List[Finding] = field(default_factory=list)

    def as_dict(self) -> Dict:
        return {
            "name": self.name,
            "grid": list(self.grid),
            "dimension_semantics": (
                list(self.dimension_semantics)
                if self.dimension_semantics is not None else None
            ),
            "revisited_axes": {k: v for k, v in sorted(self.revisited_axes.items())},
            "findings": [f.as_dict() for f in sorted(self.findings)],
        }


def find_pallas_calls(closed) -> Iterator[object]:
    """Yield every pallas_call eqn in a ClosedJaxpr (any nesting depth)."""
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn


def _dimension_semantics(params) -> Optional[Tuple[str, ...]]:
    """Declared dimension_semantics of a pallas_call — the Mosaic
    ``CompilerParams`` under ``compiler_params["mosaic_tpu"]`` — else None."""
    mosaic = (params.get("compiler_params") or {}).get("mosaic_tpu")
    ds = getattr(mosaic, "dimension_semantics", None)
    return tuple(str(x) for x in ds) if ds is not None else None


def _eval_index_map(bm, point: Sequence[int]) -> Tuple[int, ...]:
    closed = bm.index_map_jaxpr
    out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *point)
    return tuple(int(x) for x in out)


def _grid_samples(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    per_axis = []
    for size in grid:
        pts = sorted({0, size // 2, size - 1})[:_AXIS_SAMPLES]
        per_axis.append(pts)
    return list(itertools.product(*per_axis))


def _dependent_axes(bm, grid: Sequence[int]) -> List[int]:
    """Axes the block index depends on (probed per axis from the origin —
    exact for affine index maps)."""
    base = tuple(0 for _ in grid)
    base_out = _eval_index_map(bm, base)
    dep = []
    for d, size in enumerate(grid):
        for val in sorted({1, size // 2, size - 1}):
            if val == 0:
                continue
            pt = list(base)
            pt[d] = val
            if _eval_index_map(bm, tuple(pt)) != base_out:
                dep.append(d)
                break
    return dep


def _block_dims(bm) -> List[Optional[int]]:
    """Block shape as ints (None for squeezed/element/bounded-slice dims)."""
    return [
        int(b.block_size) if isinstance(b, pl.Blocked) else None
        for b in bm.block_shape
    ]


def check_pallas_call(eqn, kernel_name: str) -> KernelReport:
    """Run PL101-PL104 over one pallas_call eqn."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    sem = _dimension_semantics(eqn.params)
    report = KernelReport(name=kernel_name, grid=grid, dimension_semantics=sem)

    sem_of = (lambda d: sem[d]) if sem is not None and len(sem) == len(grid) \
        else (lambda d: None)

    for bi, bm in enumerate(gm.block_mappings):
        origin = getattr(bm, "origin", "")
        is_output = "output" in str(origin)
        label = f"kernel:{kernel_name}/{origin or f'operand[{bi}]'}"
        arr_shape = tuple(int(s) for s in bm.array_aval.shape)
        blocks = _block_dims(bm)

        # PL103 — divisibility per (padded) array dim.  Every memory
        # space Mosaic exposes (VMEM/SMEM/ANY) requires whole blocks in
        # this codebase's padded-layout regime; a remainder block means
        # a silent partial-tile read/write.
        for d, (a, b) in enumerate(zip(arr_shape, blocks)):
            if b is not None and b > 0 and a % b != 0:
                report.findings.append(
                    Finding(
                        "PL103", "error", f"{label}/dim[{d}]",
                        f"block shape {b} does not divide array dim "
                        f"{a} (axis {d}); pad the operand or shrink "
                        "the block",
                    )
                )

        # PL102 — index map stays inside the array's block extent at
        # every sampled grid point.
        extents = [
            (-(-a // b) if (b and b > 0) else None)
            for a, b in zip(arr_shape, blocks)
        ]
        oob_reported = False
        for pt in _grid_samples(grid):
            idx = _eval_index_map(bm, pt)
            for d, (i, ext) in enumerate(zip(idx, extents)):
                if ext is None:
                    continue
                if i < 0 or i >= ext:
                    report.findings.append(
                        Finding(
                            "PL102", "error", f"{label}/dim[{d}]",
                            f"index map yields block index {i} at grid "
                            f"point {tuple(pt)} but axis {d} has only "
                            f"{ext} block(s)",
                        )
                    )
                    oob_reported = True
                    break
            if oob_reported:
                break

        # PL101 / PL104 — revisited output axes vs. declared semantics.
        if is_output:
            dep = set(_dependent_axes(bm, grid))
            revisited = [d for d, size in enumerate(grid)
                         if size > 1 and d not in dep]
            if revisited:
                report.revisited_axes[f"out[{bi}]"] = revisited
            for d in revisited:
                s = sem_of(d)
                if s == "parallel":
                    report.findings.append(
                        Finding(
                            "PL101", "error", f"{label}/axis[{d}]",
                            f"output block revisited along grid axis {d} "
                            "which is declared parallel — write-write race",
                        )
                    )
                elif s is None:
                    report.findings.append(
                        Finding(
                            "PL104", "warning", f"{label}/axis[{d}]",
                            f"output block revisited along grid axis {d} "
                            "with no declared dimension_semantics; safe "
                            "only by Mosaic's implicit sequential default "
                            "— declare the axis 'arbitrary'",
                        )
                    )
    return report


def check_jaxpr_kernels(closed, kernel_name: str) -> List[KernelReport]:
    """Check every pallas_call reachable from a traced callable."""
    return [
        check_pallas_call(eqn, kernel_name)
        for eqn in find_pallas_calls(closed)
    ]
