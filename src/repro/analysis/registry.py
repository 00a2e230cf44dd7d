"""Audit registry: what the analyzer runs over, and what it may ignore
(DESIGN.md §15).

Three declarative tables:

* the **audit matrix** — every (driver, mode, backend, K) the session
  layer can compile, traced at a production-scale bucket so byte
  thresholds (closure consts, donation candidates) are meaningful;
* the **loop-census budgets** — the declared gather/scatter counts per
  (driver, mode, backend) loop body (JX005).  These are the measured
  lowerings of the keyed segment reductions; a count above budget means
  a new scatter/gather joined a hot loop undeclared;
* the **suppressions** — reviewed exemptions with design rationale.

Keeping all three next to each other makes the audit surface diffable:
adding a mode, raising a budget, or suppressing a finding is a one-line
reviewed change here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from .findings import Suppression

__all__ = [
    "AUDIT_BUCKET",
    "AUDIT_BATCH",
    "AUDIT_TICK_ITERS",
    "MODES",
    "BACKENDS",
    "KS",
    "DRIVERS",
    "DriverSpec",
    "loop_budget",
    "SUPPRESSIONS",
    "KERNEL_NAMES",
]

#: Production-representative bucket (capacity, n_hoods, n_regions) the
#: jaxpr audit traces against.  Tracing cost is shape-independent, so
#: auditing at serving scale is free — and necessary: the donation lint
#: (JX004) thresholds on real aval sizes.
AUDIT_BUCKET: Tuple[int, int, int] = (65536, 4096, 4096)
AUDIT_BATCH = 8
AUDIT_TICK_ITERS = 4

MODES: Tuple[str, ...] = ("faithful", "static", "static-pallas")
BACKENDS: Tuple[str, ...] = ("xla", "pallas-interpret")
KS: Tuple[int, ...] = (2, 3, 5)


@dataclass(frozen=True)
class DriverSpec:
    """One jitted driver the session layer compiles."""

    name: str           # "run_em" | "run_em_batched" | "run_em_ticked"
    batched: bool       # takes a leading batch axis
    ticked: bool        # takes (hoods, model, TickState)


DRIVERS: Tuple[DriverSpec, ...] = (
    DriverSpec("run_em", batched=False, ticked=False),
    DriverSpec("run_em_batched", batched=True, ticked=False),
    DriverSpec("run_em_ticked", batched=True, ticked=True),
)

#: Pallas kernels registered in kernels/ops.py that the checker audits.
KERNEL_NAMES: Tuple[str, ...] = (
    "segment_reduce", "mrf_min_energy", "fused_map_step", "fused_em_tick",
    "flash_attention",
)

# ---------------------------------------------------------------------------
# JX005 loop-census budgets.
#
# Measured lowerings (CPU trace at the aligned AUDIT_BUCKET shapes), maxed
# over K in {2, 3, 5}:
#   - static (every driver, the ticked pool included): the MAP iteration
#     reduces over the plan's sorted runs (DESIGN.md §13), so the loops'
#     scatters are the M-step's three label-keyed segment sums plus two
#     .at[].set's, and the gathers are labels[vertex], the hood-end read,
#     the argmin in vertex order and K-1 reads at the vertex bounds — 7 at
#     K=5.
#   - faithful: the same, plus the sort pipeline's per-element scatter-min
#     and its replication gather.
#   - static-pallas: the fused EM-tick route (DESIGN.md §16) folds the
#     per-label count pass into the launch.  At the audit bucket the
#     one-hot VMEM guard routes the tick to the xla reference composition,
#     whose compound-key count reduction is K-independent — 9 scatters flat
#     over K.  Every ticked driver hoists the slots' context out of its
#     loop, so its census is the serial driver's.
# The two backends lower identically at aligned shapes (the interpret
# flag changes execution, not the traced program), so each mode's row is
# duplicated per backend.  A combo missing from this table gets budget
# None (census-only) — add a row when adding a mode/backend, or the
# sentinel can't gate it.
# ---------------------------------------------------------------------------
_MODE_BUDGETS: Dict[Tuple[str, str], Dict[str, int]] = {
    ("run_em", "faithful"): {"scatter": 6, "gather": 7},
    ("run_em_batched", "faithful"): {"scatter": 6, "gather": 7},
    ("run_em_ticked", "faithful"): {"scatter": 6, "gather": 7},
    ("run_em", "static"): {"scatter": 5, "gather": 7},
    ("run_em_batched", "static"): {"scatter": 5, "gather": 7},
    ("run_em_ticked", "static"): {"scatter": 5, "gather": 7},
    ("run_em", "static-pallas"): {"scatter": 9, "gather": 2},
    ("run_em_batched", "static-pallas"): {"scatter": 9, "gather": 2},
    ("run_em_ticked", "static-pallas"): {"scatter": 9, "gather": 2},
}

_LOOP_BUDGETS: Dict[Tuple[str, str, str], Dict[str, int]] = {
    (drv, mode, backend): budget
    for (drv, mode), budget in _MODE_BUDGETS.items()
    for backend in BACKENDS
}


def loop_budget(driver: str, mode: str, backend: str) -> Optional[Dict[str, int]]:
    return _LOOP_BUDGETS.get((driver, mode, backend))


# ---------------------------------------------------------------------------
# Suppressions — every exemption cites its design contract.
# ---------------------------------------------------------------------------
SUPPRESSIONS: Tuple[Suppression, ...] = (
    Suppression(
        code="JX004",
        # NB: fnmatch treats [...] as a character class, so the glob must
        # not spell the literal brackets of the site string.
        site_pattern="run_em_ticked*",
        reason=(
            "deliberate: the ticked pool state is NOT donated so the "
            "serving engine can replay the identical state after a failed "
            "tick execute (fallback replay-exactness, DESIGN.md §14); "
            "donating TickState would corrupt the retry path"
        ),
    ),
)
