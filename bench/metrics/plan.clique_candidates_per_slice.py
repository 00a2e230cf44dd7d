"""Vertices the clique enumerator tested for adjacency (``LEDGER``
``plan.clique_candidates``), per ``plan`` span, over the recording the
program keeps while the profiler traces (``repro.obs.traced()``).  A program
that keeps no such counter gives None."""

import math


def read(run):
    rec = getattr(run, "recorder", None)
    if rec is None:
        try:
            from repro import obs
        except ImportError:
            return None
        rec = obs.traced()
    if rec is None:
        return None
    grown = rec.ledger_delta().get("plan", {}).get("clique_candidates")
    plans = [r for r in rec.records if r.name == "plan" and not math.isnan(r.end)]
    if not grown or not plans:
        return None
    return grown / len(plans)
