"""Finding/report types shared by every analyzer (DESIGN.md §15).

A *finding* is one statically-detected defect candidate, identified by a
stable detector code, the site it was found at, and a human-readable
message.  Findings are value objects: deterministic, orderable, and
JSON-serializable, so the checked-in ``ANALYSIS.json`` baseline diffs
cleanly and CI can gate on "no new unsuppressed findings".

Detector codes (the taxonomy; one class per failure mode):

==========  ============================================================
``JX001``   implicit dtype promotion (same-kind widening, or any >32-bit
            leak) on a traced value path
``JX002``   host callback / debug print inside a ``while``/``scan`` body
``JX003``   trace-embedded closure constant above the size threshold
``JX004``   large non-donated input whose aval matches an output
            (donation candidate — the buffer could be reused in place)
``JX005``   gather/scatter census in loop bodies exceeds the declared
            per-driver budget (a keyed segment reduction candidate
            slipped in as a scatter, or a new gather joined the loop)
``PL101``   Pallas output block revisited along a grid axis declared
            ``parallel`` (a write-write race off TPU's sequential grid)
``PL102``   Pallas BlockSpec index map escapes the array's block extent
``PL103``   Pallas block shape does not divide the (padded) array shape
``PL104``   Pallas output block revisited along a grid axis with NO
            declared dimension semantics (safe only by Mosaic's implicit
            sequential default — declare it)
``BG001``   a measured phase exceeded its declared retrace/compile budget
==========  ============================================================

Severity is ``error`` for defects that corrupt results (races, bounds,
budget blowouts) and ``warning`` for latent hazards (undeclared
semantics, donation candidates).  ``--check`` gates on BOTH: the
baseline must carry zero unsuppressed findings of any severity.
"""

from __future__ import annotations

import fnmatch
import json
from dataclasses import dataclass, field, asdict
from typing import Dict, List, Sequence, Tuple

__all__ = [
    "Finding",
    "Suppression",
    "apply_suppressions",
    "report_to_json",
]


@dataclass(frozen=True, order=True)
class Finding:
    """One statically-detected defect candidate."""

    code: str       # detector code, e.g. "PL101"
    severity: str   # "error" | "warning"
    site: str       # where: "run_em[static/xla/K=2]" or "kernel:segment_reduce/out[0]"
    message: str    # human-readable, deterministic (no addresses/timings)
    suppressed_by: str = ""  # reason string when a suppression matched

    @property
    def suppressed(self) -> bool:
        return bool(self.suppressed_by)

    def as_dict(self) -> Dict[str, str]:
        return asdict(self)


@dataclass(frozen=True)
class Suppression:
    """A declared, reviewed exemption: (code, site glob) -> reason.

    Suppressions are code, not config — they live in
    ``repro.analysis.registry`` next to the audit matrix so every
    exemption carries its design rationale and shows up in review when
    added.  A suppression with zero matches in a full audit is itself
    reported (stale suppressions rot).
    """

    code: str           # exact detector code
    site_pattern: str   # fnmatch glob over Finding.site
    reason: str         # why this finding is deliberate (cite DESIGN.md)

    def matches(self, finding: Finding) -> bool:
        return finding.code == self.code and fnmatch.fnmatchcase(
            finding.site, self.site_pattern
        )


def apply_suppressions(
    findings: Sequence[Finding], suppressions: Sequence[Suppression]
) -> Tuple[List[Finding], List[Suppression]]:
    """Mark suppressed findings; return (findings, stale_suppressions)."""
    used = set()
    out: List[Finding] = []
    for f in findings:
        reason = ""
        for i, s in enumerate(suppressions):
            if s.matches(f):
                reason = s.reason
                used.add(i)
                break
        out.append(
            Finding(f.code, f.severity, f.site, f.message, suppressed_by=reason)
            if reason
            else f
        )
    stale = [s for i, s in enumerate(suppressions) if i not in used]
    return out, stale


def report_to_json(report: Dict) -> str:
    """Serialize a report dict deterministically (sorted keys, no floats
    that vary run-to-run — callers must keep timings out)."""
    return json.dumps(report, indent=2, sort_keys=True) + "\n"
