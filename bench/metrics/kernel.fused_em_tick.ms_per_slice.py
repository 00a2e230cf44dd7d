"""Device milliseconds of the ``fused_em_tick`` kernel per solved slice,
from the trace."""

KERNEL = "fused_em_tick"


def read(run):
    if run.trace is None or not run.completed:
        return None
    events, seconds = run.trace.kernel(KERNEL)
    return 1e3 * seconds / run.completed if events else None
