"""The serving engine's online tick-cost fit (DESIGN.md §17).

:class:`DecayedAffineFit` is the exponentially-decayed least-squares fit
of ``cost(x) ~= a + b*x`` the engine runs over its (steps, tick-duration)
observations for ``tick_iters="auto"``.  Pure Python: it runs on the host
between device ticks.
"""

from __future__ import annotations

from typing import Tuple

__all__ = ["DecayedAffineFit"]


class DecayedAffineFit:
    """Exponentially-decayed least squares of ``y ~= a + b*x``.

    ``observe(x, y)`` decays all accumulated moments by ``decay`` and adds
    the new sample, so recent observations dominate (the serving engine's
    per-tick cost drifts with load and cache temperature).  ``fit()``
    solves the decayed normal equations; degenerate cases (fewer than two
    effective samples, zero variance in ``x``) fall back first to a
    mean-split heuristic (30% of the mean cost as fixed, the rest
    marginal) and finally to ``default``.

    The intercept can be floored (``a_floor``): the engine passes its
    measured per-tick host overhead, because an unfloored fit over a run
    of small-tick observations can drive ``a`` to zero and lock the
    adaptive policy permanently into the smallest tick size
    (DESIGN.md §17).
    """

    def __init__(self, decay: float = 0.95):
        if not 0.0 < decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay}")
        self.decay = decay
        # Decayed moments: sample count, sum x, sum y, sum x^2, sum x*y.
        self._n = 0.0
        self._sx = 0.0
        self._sy = 0.0
        self._sxx = 0.0
        self._sxy = 0.0
        self.observations = 0   # undecayed count, for introspection

    def observe(self, x: float, y: float) -> None:
        d = self.decay
        self._n = self._n * d + 1.0
        self._sx = self._sx * d + x
        self._sy = self._sy * d + y
        self._sxx = self._sxx * d + x * x
        self._sxy = self._sxy * d + x * y
        self.observations += 1

    def fit(
        self,
        *,
        a_floor: float = 0.0,
        b_min: float = 1e-6,
        default: Tuple[float, float] = (5e-3, 5e-3),
    ) -> Tuple[float, float]:
        n, sx, sy, sxx, sxy = self._n, self._sx, self._sy, self._sxx, self._sxy
        if n >= 2.0:
            var = sxx - sx * sx / n
            if var > 1e-9:
                b = (sxy - sx * sy / n) / var
                b = max(b, b_min)
                a = max((sy - b * sx) / n, a_floor)
                return a, b
        if n > 0.0:
            mean_x = sx / n
            mean_y = sy / n
            if mean_x > 0:
                return max(0.3 * mean_y, a_floor), max(0.7 * mean_y / mean_x, b_min)
        return max(default[0], a_floor), max(default[1], b_min)

