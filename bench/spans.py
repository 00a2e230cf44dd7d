"""The program's own spans (``repro.obs``) in a traced run, for the
per-layer metrics that read them.

A traced window runs under the JAX profiler, and while the profiler traces
the program keeps each span it opens (``repro.obs.traced()``): its name,
start and end on the host clock, its parent, and the XLA compiles that ran
in it.  A program without ``repro.obs`` has no such spans; every reader
built on this module then returns None.
"""

from __future__ import annotations

import math
from typing import List, Optional


def records(run) -> Optional[list]:
    """The program's closed spans inside the run's window (``bench.window``,
    present in traced runs), or None where the program records none."""
    rec = getattr(run, "recorder", None)
    if rec is None:
        try:
            from repro import obs
        except ImportError:
            return None
        rec = obs.traced()
    if rec is None:
        return None
    w0, w1 = next(((a, b) for name, a, b in run.spans if name == "window"),
                  (-math.inf, math.inf))
    return [r for r in rec.records if w0 <= r.start and r.end <= w1]


def _named(recs: List, name: str) -> List:
    return [r for r in recs if r.name == name]


def per_plan(run, name: str) -> Optional[float]:
    """Seconds in the span ``name`` per ``plan`` span of the window."""
    recs = records(run)
    if not recs:
        return None
    plans, spans = _named(recs, "plan"), _named(recs, name)
    if not plans or not spans:
        return None
    return sum(r.seconds for r in spans) / len(plans)


def plan_compile_s(run) -> Optional[float]:
    """XLA compile seconds that ran in ``plan`` or one of its stages, per
    ``plan`` span of the window."""
    recs = records(run)
    if not recs:
        return None
    plans = _named(recs, "plan")
    if not plans:
        return None
    inside = [r for r in recs if r.name == "plan" or r.name.startswith("plan.")]
    return sum(r.compile_s for r in inside) / len(plans)


def per_slice(run, name: str, scale: float = 1.0) -> Optional[float]:
    """Seconds (times ``scale``) in the span ``name`` per slice the window
    completed."""
    recs = records(run)
    if not recs or not run.completed:
        return None
    spans = _named(recs, name)
    if not spans:
        return None
    return scale * sum(r.seconds for r in spans) / run.completed
