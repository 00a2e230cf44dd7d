"""Benchmark harness: runs one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell sits in a file of its own, found by name:

* ``bench/configs/<config>.json``   the deployment, as it is run;
* ``bench/traffic/<mix>.json``      the name of its driver and the driver's
                                    parameters;
* ``bench/drivers/<driver>.py``     a kind of traffic (``bench/drivers/__init__.py``);
* ``bench/metrics/<metric>.py``     a reader with ``read(run) -> float | None``;
                                    a metric ``<name>.<variant>`` without a
                                    file of its own is read by ``<name>.py``;
* ``bench/limits/<cell>.json``      the limit of each number the check compares.

A run: set-up (data from the seed, planning, warm-up, compiles) is timed as
``setup_s``; then the window drives the traffic for ``--seconds`` with JAX's
persistent compilation cache off, so that whatever compiles there compiles
in every run alike; then the check compares the window's answers with
``bench/reference.py``.  With ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics instead of the
end-to-end ones.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import warnings
from typing import List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: JAX's persistent compilation cache, at a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"
#: JAX's monitoring event around each XLA compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def load_config(name: str) -> dict:
    return _load(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _load(BENCH / "traffic" / f"{name}.json")


def load_limits(cell: str) -> dict:
    return _load(BENCH / "limits" / f"{cell}.json")


def load_reader(metric: str):
    """``read`` of ``bench/metrics/<metric>.py``, or of the file of the
    longest dotted prefix of the name that has one: the variants of one
    quantity split by cell (``device.idle_share.solve``) share a reader."""
    name = metric
    while not (BENCH / "metrics" / f"{name}.py").exists() and "." in name:
        name = name.rsplit(".", 1)[0]
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_session(cfg: dict):
    """The program's ``Segmenter`` as the configuration states it, with the
    backend fallback off: a run never measures a path it did not ask for."""
    from repro import api

    return api.Segmenter(api.ExecutionConfig(
        mode=cfg["mode"], precision=cfg["precision"], n_labels=cfg["n_labels"],
        overseg_grid=tuple(cfg["overseg_grid"]), overseg_iters=cfg["overseg_iters"],
        beta=cfg["beta"], sigma_min=cfg["sigma_min"], init=cfg["init"],
        max_em_iters=cfg["max_em_iters"], max_map_iters=cfg["max_map_iters"],
        capacity_bucket=cfg["capacity_bucket"], segment_bucket=cfg["segment_bucket"],
        fallback=api.FallbackPolicy(enabled=False),
    ))


def metrics_of(bench: dict, cell: str, kind: str) -> List[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics.  A metric without
    ``workloads`` belongs to every cell that reports the metric it moves
    (per-layer) or to every cell (end-to-end)."""
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in metrics_of(bench, cell, "end_to_end")}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e else [])]


# ---------------------------------------------------------------------------
# spans, compiles, cache
# ---------------------------------------------------------------------------


class Recorder:
    """Host spans (also written into the profiler trace as annotations) and
    the XLA compiles that happen while ``on``."""

    def __init__(self):
        self.spans: list = []      # (name, start, end), perf_counter seconds
        self.compiles: list = []   # (time, seconds)
        self.on = False

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if self.on:
            self.spans.append((name, t0, time.perf_counter()))

    def on_compile(self, event, seconds, **_):
        if self.on and event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), seconds))


def use_persistent_cache(on: bool) -> None:
    """Turn JAX's persistent compilation cache on or off for what compiles
    next (the cache object is rebuilt from the config on the next use)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def configure_cache(cache_dir: pathlib.Path = CACHE_DIR) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    use_persistent_cache(True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader gets."""

    config: dict
    spans: list
    compiles: list
    answers: list
    completed: int
    window_s: float
    launch: dict           # {"bucket": [...], "batch": n}
    plans: list            # natural (n_elements, n_hoods, n_regions) per distinct slice
    trace: Optional[object] = None   # trace_reduce.Summary
    peaks: Optional[dict] = None     # bench/peaks.json entry of this device


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None,
             config: Optional[dict] = None, cache_dir: pathlib.Path = CACHE_DIR) -> dict:
    """One run of one cell; returns the result object the CLI prints.
    ``config`` replaces the configuration file (the tests run tiny copies);
    ``require_tpu=False`` skips the look for a chip (tests only)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark()
    cell = next((c for c in bench["workloads"] if c["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg = config or load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])
    limits = load_limits(workload)

    import jax

    from bench import check, drivers, trace_reduce
    from repro.kernels.ops import FusedKernelDowngradeWarning

    device = device_info(cell["chips"], require_tpu)
    configure_cache(cache_dir)
    if cfg["mode"] == "static-pallas":
        # The configuration states the fused kernel: a downgrade is a fault.
        warnings.simplefilter("error", FusedKernelDowngradeWarning)

    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_compile)
    sess = make_session(cfg)
    window = drivers.load(traffic["driver"]).setup(sess, cfg, traffic, seed, rec)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:  # a traced window may be shorter: its per-layer metrics are per slice
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    use_persistent_cache(False)
    window.prime()
    rec.on = True
    setup_s = time.perf_counter() - t_start
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(jax.profiler.trace(
                tdir, profiler_options=trace_reduce.profile_options()))
            stack.enter_context(rec.span("window"))
        answers, attempted, failed, window_s = drivers.run_window(window, seconds)
    rec.on = False
    use_persistent_cache(True)
    jax.monitoring.unregister_event_duration_listener(rec.on_compile)

    for name in sorted({n for n, _, _ in rec.spans}):
        d = [b - a for n, a, b in rec.spans if n == name]
        print(f"span {name}: n={len(d)} mean={sum(d) / len(d):.4f} s min={min(d):.4f} "
              f"max={max(d):.4f} first={d[0]:.4f}", file=sys.stderr)
    device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
    summary = None
    if trace:
        summary = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    completed = sum(a.status in ("converged", "max_iters") for a in answers)
    failed += len(answers) - completed
    plans = [s.plan for s in window.slices if s.plan is not None]
    run = Run(config=cfg, spans=rec.spans,
              compiles=rec.compiles, answers=answers, completed=completed,
              window_s=window_s, launch=window.launch,
              plans=[_shape(p) for p in plans], trace=summary,
              peaks=trace_reduce.peaks_for(device["kind"]) if trace else None)

    metrics = {}
    if trace:
        for m in metrics_of(bench, workload, "per_layer"):
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        rate = traffic["rate_metric"]
        for m in metrics_of(bench, workload, "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] == rate:
                metrics[rate] = {"value": completed / window_s, "unit": m["unit"]}

    # The check runs after the window closed and the peak was read, on the
    # program's answers alone; the program's state is dropped first.
    slices = window.slices
    del sess, window, plans, run
    gc.collect()
    numbers = check.compare(answers, slices, cfg)
    correct = failed == 0 and completed > 0 and check.within(numbers, limits)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    return out


def _shape(plan) -> dict:
    h = plan.problem.hoods
    return {"n_elements": h.n_elements, "n_hoods": h.n_hoods, "n_regions": h.n_regions}
