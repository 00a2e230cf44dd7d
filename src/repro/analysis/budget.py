"""Compile/trace budget ledger + per-phase sentinel (DESIGN.md §15).

One process-global :class:`Ledger` of monotonically-increasing counters,
grouped into named *sections*.  It is THE backing store for every
trace/compile/tick counter in the codebase — the three previously
independent stores now alias it and cannot drift:

=========  ==========================================================
section    who writes it
=========  ==========================================================
"trace"    ``em.TRACE_COUNTS`` *is* this section's dict (same object);
           the jitted drivers bump it at trace time, ``distributed``
           bumps ``run_em_sharded``
"compile"  ``api.session`` records every ``lower().compile()``
           (``lower_compile``) and every warm LRU hit (``warm_hit``)
"serve"    the serving engine records ``ticks`` and ``lane_steps``
"plan"     ``hoods.build_hoods`` records ``hood_class_miss`` (the first
           build of a shape class in the process) and ``hood_class_hit``;
           ``cliques.enumerate_maximal_cliques`` ``clique_candidates`` (the
           vertices it tested for adjacency to a clique's members)
=========  ==========================================================

On top of the ledger sit *declared phase budgets*: the zero-retrace /
one-compile contracts that tests previously asserted ad hoc against
``em.TRACE_COUNTS`` become named :class:`PhaseBudget` rows, and
``expect(phase)`` turns any overshoot into a typed error the analysis
CLI reports as a ``BG001`` finding.

This module is imported by ``core.pmrf.em`` at import time, so it must
stay dependency-free (stdlib only — no jax, no repro siblings).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

__all__ = [
    "Ledger",
    "LEDGER",
    "PhaseBudget",
    "BUDGETS",
    "budget_for",
    "expect",
    "reset_all",
    "BudgetExceeded",
]


class BudgetExceeded(AssertionError):
    """A measured phase burned more traces/compiles than it declared."""

    def __init__(self, phase: str, section: str, delta: int, max_delta: int):
        self.phase, self.section = phase, section
        self.delta, self.max_delta = delta, max_delta
        super().__init__(
            f"phase {phase!r} used {delta} {section} event(s); "
            f"budget allows {max_delta}"
        )


class Ledger:
    """Named sections of named int counters.

    ``section()`` hands out the *live* dict, so legacy counter stores
    (``em.TRACE_COUNTS``) can alias a section directly: incrementing the
    dict IS incrementing the ledger.  Resets zero values in place —
    section identity is stable for the life of the process, which is
    what lets module-level aliases keep working across resets.
    """

    def __init__(self) -> None:
        self._sections: Dict[str, Dict[str, int]] = {}

    def section(self, name: str, keys: Tuple[str, ...] = ()) -> Dict[str, int]:
        sec = self._sections.setdefault(name, {})
        for k in keys:
            sec.setdefault(k, 0)
        return sec

    def bump(self, section: str, key: str, n: int = 1) -> int:
        sec = self.section(section)
        sec[key] = sec.get(key, 0) + n
        return sec[key]

    def total(self, section: str) -> int:
        return sum(self._sections.get(section, {}).values())

    def reset(self, section: Optional[str] = None) -> None:
        sections = (
            [self._sections[section]] if section in self._sections
            else ([] if section is not None else list(self._sections.values()))
        )
        for sec in sections:
            for k in sec:
                sec[k] = 0

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        return {name: dict(sec) for name, sec in sorted(self._sections.items())}


#: The process-global ledger every counter in the repo writes through.
LEDGER = Ledger()


def reset_all() -> None:
    """Zero every counter in every section (the one test-reset hook)."""
    LEDGER.reset()


@dataclass(frozen=True)
class PhaseBudget:
    """A declared ceiling on one section's event count during a phase."""

    phase: str      # name, e.g. "warm_execute"
    section: str    # ledger section the ceiling applies to
    max_delta: int  # inclusive ceiling on the section total's growth
    note: str       # the contract this formalizes (cite DESIGN.md)


#: The repo's declared retrace/compile contracts.  These are the budgets
#: the ad-hoc ``em.TRACE_COUNTS`` test assertions enforced implicitly;
#: the analysis CLI measures each one against a live smoke scenario.
BUDGETS: Tuple[PhaseBudget, ...] = (
    PhaseBudget(
        "cold_compile", "trace", 1,
        "a cold ExecutableKey traces its driver exactly once (DESIGN.md §10)",
    ),
    PhaseBudget(
        "warm_execute", "trace", 0,
        "a warm LRU hit performs zero driver traces (DESIGN.md §10)",
    ),
    PhaseBudget(
        "warm_tick", "trace", 0,
        "advancing a warm ticked pool performs zero traces — admission, "
        "ticks, and retirement are pure data ops (DESIGN.md §12)",
    ),
)

_BY_NAME = {b.phase: b for b in BUDGETS}


def budget_for(phase: str) -> PhaseBudget:
    return _BY_NAME[phase]


@contextmanager
def expect(phase: str):
    """Assert the wrapped block stays within ``phase``'s declared budget."""
    b = budget_for(phase)
    before = LEDGER.total(b.section)
    yield
    delta = LEDGER.total(b.section) - before
    if delta > b.max_delta:
        raise BudgetExceeded(b.phase, b.section, delta, b.max_delta)
