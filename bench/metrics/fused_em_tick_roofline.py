"""Share of its roofline that the fused EM-tick kernel reaches.

The least time for the tick's own work at the chip's peaks (the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth) over the
kernel's device time in the trace.  The work is counted from the problem,
not from the implementation: per lane and tick, over the lane's real hood
elements N, hoods S and regions R, with K labels,

* operations: per element, the label counts (K adds), the K energies
  (14 operations each: difference, square, scale, log term, weight, the
  smoothness count, clamp, normalisation and sum), the min/argmin fold
  (K), the hood energy sum and the vote (2); per region the plurality
  vote (K) and the three M-step sums (5); per hood the convergence window
  (3 * 3 compares and the scale, 10);
* bytes, each operand and result once: per element y, w, the hood size,
  the current label, the valid flag, hood id and vertex id (7 x 4 B); per
  region its mean and weight in, its label and K votes out ((3 + K) x 4 B);
  per hood its 4-deep energy history in and its energy out (5 x 4 B).

The one-hot MXU contractions the kernel uses to compute this are not
counted.  Each kernel event is one launch over the window's ``batch``
lanes, the plans of the window's distinct slices.
"""

KERNEL = "fused_em_tick"


def ops_and_bytes(n: int, s: int, r: int, k: int):
    ops = n * (k + 14 * k + k + 2) + r * (k + 5) + s * 10
    nbytes = 4 * (7 * n + (3 + k) * r + 5 * s)
    return ops, nbytes


def read(run):
    if run.trace is None or run.peaks is None or not run.plans:
        return None
    events, seconds = run.trace.kernel(KERNEL)
    if not events:
        return None
    k = run.config["n_labels"]
    per = [ops_and_bytes(p["n_elements"], p["n_hoods"], p["n_regions"], k) for p in run.plans]
    lanes = run.launch["batch"]
    ops = events * lanes * sum(o for o, _ in per) / len(per)
    nbytes = events * lanes * sum(b for _, b in per) / len(per)
    least = max(ops / run.peaks["flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
