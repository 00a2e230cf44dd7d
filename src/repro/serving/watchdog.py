"""Step-time straggler detection, shared by the segmentation serving
engine (one observation per tick) and the training loop (one per step).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class StragglerWatchdog:
    """Step-time EWMA + deadline detector."""

    alpha: float = 0.1           # EWMA smoothing
    threshold: float = 3.0       # multiple of EWMA that flags a straggler
    warmup_steps: int = 5        # compile/first-steps excluded
    ewma: Optional[float] = None
    _seen: int = 0
    events: List[Dict[str, float]] = field(default_factory=list)

    def observe(self, step: int, seconds: float) -> bool:
        """Record a step time; returns True if the step was straggler-slow."""
        self._seen += 1
        if self._seen <= self.warmup_steps:
            return False
        if self.ewma is None:
            self.ewma = seconds
            return False
        slow = seconds > self.threshold * self.ewma
        if slow:
            self.events.append(
                {"step": step, "seconds": seconds, "ewma": self.ewma}
            )
        # EWMA excludes flagged outliers so one straggler doesn't mask the next.
        if not slow:
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * seconds
        return slow

    @property
    def deadline_seconds(self) -> Optional[float]:
        return None if self.ewma is None else self.threshold * self.ewma
