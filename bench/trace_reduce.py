"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced run wraps its window in a host annotation ``bench.window`` and
each unit of traffic in ``bench.<span>`` annotations (``bench/harness.py``).
From the trace this module takes:

* ``window_s``: the length of ``bench.window``;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the window and averaged over the devices that ran anything;
* per-operation device time, by HLO instruction name (``%fused_em_tick.7``),
  for kernel times and the ``device_ops`` of the breakdown;
* the idle gaps between operations, each named by the innermost
  ``bench.*`` host span in progress at its midpoint.

Peaks for roofline shares come from ``bench/peaks.json``, keyed by the
device kind JAX reports.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import pathlib
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: Control-flow operations span the operations they run: they count towards
#: busy time, but not as operations of their own.
CONTAINERS = re.compile(r"^%(while|conditional|call)\b")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def profile_options():
    """The benchmark's host annotations, without Python function tracing or
    the runtime's own host events, which slow the host code the window
    measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: Dict[str, Tuple[int, float]]        # name -> (events, device seconds)
    gaps: List[Tuple[str, float]]            # (host span, seconds), longest first

    def kernel(self, name: str) -> Tuple[int, float]:
        """(events, device seconds) of the operations whose name holds
        ``name``, summed over devices."""
        n = t = 0
        for op, (k, s) in self.ops.items():
            if name in op:
                n, t = n + k, t + s
        return n, t

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        return {"device_ops": [[name, s] for name, (_, s) in top],
                "idle_gaps": [list(g) for g in self.gaps[:10]]}


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce_events(device_ops: Dict[str, list], host_spans: list) -> Summary:
    """``device_ops``: device -> [(name, start_ns, end_ns)]; ``host_spans``:
    [(name, start_ns, end_ns)] of ``bench.*`` annotations, the window's
    among them."""
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, found {len(windows)}")
    w0, w1 = windows[0]
    ops: Dict[str, list] = collections.defaultdict(lambda: [0, 0.0])
    busy, gaps = [], []
    spans = sorted((a, b, n[len(SPAN_PREFIX):]) for n, a, b in host_spans if n != WINDOW_SPAN)
    for events in device_ops.values():
        clipped = [(max(a, w0), min(b, w1), n) for n, a, b in events if b > w0 and a < w1]
        if not clipped:
            continue
        for a, b, n in clipped:
            if not CONTAINERS.match(n):
                ops[n][0] += 1
                ops[n][1] += (b - a) * 1e-9
        merged = _union((a, b) for a, b, _ in clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), (b - a) * 1e-9))
    if not busy:
        raise ValueError("no device operation ran inside the traced window")
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / len(busy),
                   ops={k: (v[0], v[1]) for k, v in ops.items()}, gaps=gaps)


def _span_at(spans, t) -> str:
    """The innermost (shortest) span covering ``t``."""
    best: Optional[tuple] = None
    for a, b, n in spans:
        if a > t:
            break
        if b >= t and (best is None or b - a < best[1] - best[0]):
            best = (a, b, n)
    return best[2] if best else "outside any span"


def read_xplane(path) -> Tuple[Dict[str, list], list]:
    """Device operations and ``bench.*`` host spans from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name.split(" = ")[0], e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return device_ops, host_spans


def reduce_dir(trace_dir) -> Summary:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_events(*read_xplane(files[-1]))
