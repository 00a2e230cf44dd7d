"""The per-layer metrics read off the program's own spans (``bench/spans.py``
and its readers), on hand-built runs and on tiny windows under the JAX
profiler; and the trace reduction given the program's ``repro.*`` spans."""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil
import sys

import jax
import pytest

from bench import drivers, harness, spans, trace_reduce
from repro import obs

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "g16_solve.xplane.pb.gz"
PLAN_METRICS = ["plan.slic.s_per_slice", "plan.graph.s_per_slice", "plan.cliques.s_per_slice",
                "plan.hoods.s_per_slice", "plan.compile_s_per_slice", "session.pad.s_per_slice"]
SERIAL_METRICS = ["session.launch.ms_per_slice.serial", "session.wait.ms_per_slice.serial",
                  "session.assemble.ms_per_slice.serial"]


def _run(recorder=None, completed=2, window=(0.0, 100.0)):
    run = harness.Run(config={}, spans=[("window", *window)], compiles=[], answers=[],
                      completed=completed, window_s=window[1] - window[0], launch={},
                      plans=[])
    if recorder is not None:
        run.recorder = recorder
    return run


def _rec(*rows):
    """A recorder of (name, start, end, parent index or None, compile_s)."""
    rec = obs.Recorder()
    for name, a, b, parent, compile_s in rows:
        rec.records.append(obs.Record(
            name, a, b, parent=None if parent is None else rec.records[parent],
            compiles=int(compile_s > 0), compile_s=compile_s))
    return rec


def _read(metric, run):
    return harness.load_reader(metric)(run)


def test_plan_readers_by_hand():
    rec = _rec(
        ("plan", 1.0, 11.0, None, 0.0),
        ("plan.slic", 1.0, 2.0, 0, 0.5),
        ("plan.graph", 2.0, 3.0, 0, 0.0),
        ("plan.cliques", 3.0, 3.5, 0, 0.0),
        ("plan.hoods", 3.5, 10.0, 0, 4.0),
        ("session.execute", 11.0, 12.0, None, 0.0),
        ("session.pad", 11.0, 11.6, 5, 0.25),
        ("plan", 20.0, 26.0, None, 0.0),
        ("plan.slic", 20.0, 21.0, 7, 0.0),
        ("plan.graph", 21.0, 22.0, 7, 0.0),
        ("plan.cliques", 22.0, 22.5, 7, 0.0),
        ("plan.hoods", 22.5, 25.5, 7, 2.0),
        ("session.pad", 26.0, 26.2, None, 0.0),
        ("plan", 99.0, 101.0, None, 0.0),  # ends after the window: left out
    )
    run = _run(rec)
    assert _read("plan.slic.s_per_slice", run) == pytest.approx(1.0)
    assert _read("plan.graph.s_per_slice", run) == pytest.approx(1.0)
    assert _read("plan.cliques.s_per_slice", run) == pytest.approx(0.5)
    assert _read("plan.hoods.s_per_slice", run) == pytest.approx(4.75)
    assert _read("plan.compile_s_per_slice", run) == pytest.approx(3.25)  # (0.5+4+2)/2
    assert _read("session.pad.s_per_slice", run) == pytest.approx(0.4)   # 0.8 s / 2 slices


def test_session_readers_by_hand():
    rec = _rec(*[row for k in range(4) for row in (
        ("session.execute", 10.0 * k, 10.0 * k + 9, None, 0.0),
        ("session.launch", 10.0 * k, 10.0 * k + 0.001, 4 * k, 0.0),
        ("session.wait", 10.0 * k + 1, 10.0 * k + 1.007, 4 * k, 0.0),
        ("session.assemble", 10.0 * k + 2, 10.0 * k + 2.003, 4 * k, 0.0),
    )])
    run = _run(rec, completed=4)
    assert _read("session.launch.ms_per_slice.serial", run) == pytest.approx(1.0)
    assert _read("session.wait.ms_per_slice.serial", run) == pytest.approx(7.0)
    assert _read("session.assemble.ms_per_slice.serial", run) == pytest.approx(3.0)
    # A cell whose window ran no plan reports no plan metric.
    assert _read("plan.hoods.s_per_slice", run) is None
    assert _read("plan.compile_s_per_slice", run) is None


def test_readers_without_program_spans(monkeypatch):
    """The parent program has no ``repro.obs``, or recorded nothing: every
    new metric is left out, and nothing raises."""
    run = _run(completed=3)
    monkeypatch.setattr(obs, "_traced", None)
    for metric in PLAN_METRICS + SERIAL_METRICS:
        assert _read(metric, run) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    for metric in PLAN_METRICS + SERIAL_METRICS:
        assert _read(metric, run) is None
    assert spans.records(run) is None


def test_each_new_metric_only_in_its_cell():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cells = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for metric in PLAN_METRICS:
        assert cells[metric] == ["synthetic512-g64.volume"]
    for metric in SERIAL_METRICS:
        assert cells[metric] == ["synthetic512-g16-fused.solve-serial"]


def _profiled_window(tmp_path, tiny, config, mix, seconds):
    """A tiny window of ``mix`` under the JAX profiler, as a traced run has
    it, and the run its readers get."""
    cfg = tiny(config)
    traffic = harness.load_traffic(mix)
    if mix == "volume":
        traffic["max_slices"] = 3
    rec = harness.Recorder()
    window = drivers.load(traffic["driver"]).setup(harness.make_session(cfg), cfg, traffic, 5, rec)
    rec.on = True
    with jax.profiler.trace(str(tmp_path), profiler_options=trace_reduce.profile_options()):
        with rec.span("window"):
            answers, _, failed, window_s = drivers.run_window(window, seconds)
    rec.on = False
    assert failed == 0
    return harness.Run(config=cfg, spans=rec.spans, compiles=rec.compiles, answers=answers,
                       completed=len(answers), window_s=window_s, launch=window.launch,
                       plans=[])


def test_volume_window_reports_plan_stages(tmp_path, tiny):
    run = _profiled_window(tmp_path, tiny, "synthetic512-g64", "volume", 0.5)
    values = {m: _read(m, run) for m in PLAN_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    for metric in SERIAL_METRICS:
        assert _read(metric, run) is not None  # execute runs here too
    recs = spans.records(run)
    plans = [r for r in recs if r.name == "plan"]
    assert len(plans) == len(run.answers)
    # The stages and the plan's own time add up to the program's span, and the
    # program's span sits inside the benchmark's own ``plan`` span.
    own = obs.Recorder()
    own.records = recs
    self_s = own.self_seconds()
    children = [r for r in recs if r.parent is not None and r.parent.name == "plan"]
    total = sum(r.seconds for r in plans)
    assert sum(r.seconds for r in children) + sum(self_s[id(r)] for r in plans) \
        == pytest.approx(total)
    outside = [b - a for name, a, b in run.spans if name == "plan"]
    assert sum(outside) >= total and sum(outside) == pytest.approx(total, rel=0.05)
    # The profiler's trace holds the program's spans by name.
    names = _host_span_names(tmp_path)
    assert {"repro.plan", "repro.plan.hoods", "repro.session.assemble", "bench.window"} <= names


def test_serial_window_reports_session_spans(tmp_path, tiny):
    run = _profiled_window(tmp_path, tiny, "synthetic512-g16-fused", "solve-serial", 0.3)
    values = {m: _read(m, run) for m in SERIAL_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert _read("plan.hoods.s_per_slice", run) is None  # no plan in this window
    per_slice_ms = 1e3 * run.window_s / run.completed
    assert sum(values.values()) <= per_slice_ms


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    return {e.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:") for line in plane.lines for e in line.events
            if e.name.startswith(("repro.", "bench."))}


def test_gap_named_by_innermost_program_span():
    """Given ``repro.*`` spans beside ``bench.*`` ones, an idle gap takes the
    name of the innermost span of either kind, and the device numbers stay."""
    ms = 1_000_000
    bench = [("bench.window", 0, 100 * ms), ("bench.plan", 5 * ms, 95 * ms)]
    program = [("repro.plan", 6 * ms, 94 * ms), ("repro.plan.slic", 6 * ms, 20 * ms),
               ("repro.plan.hoods", 40 * ms, 90 * ms)]
    dev = {"/device:TPU:0": [("slic", 10 * ms, 20 * ms), ("hood_arrays", 80 * ms, 85 * ms)]}
    before = trace_reduce.reduce_events(dev, bench)
    after = trace_reduce.reduce_events(dev, bench + program)
    assert [g[0] for g in before.gaps] == ["plan", "plan", "plan"]
    assert [g[0] for g in after.gaps] == ["plan.hoods", "plan", "plan"]
    assert (after.window_s, after.busy_s, after.ops) == (before.window_s, before.busy_s, before.ops)


def test_recorded_trace_unchanged_by_program_spans(tmp_path):
    """The recorded chip trace holds no ``repro.*`` span; spans added to it
    rename gaps and nothing else."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    device_ops, host = trace_reduce.read_xplane(path)
    assert not any(n.startswith("repro.") for n, _, _ in host)
    base = trace_reduce.reduce_events(device_ops, host)
    launches = [(a, b) for n, a, b in host if n == "bench.launch"]
    extra = [("repro.session.assemble", a + 1, b - 1) for a, b in launches]
    named = trace_reduce.reduce_events(device_ops, host + extra)
    assert (named.window_s, named.busy_s, named.ops) == (base.window_s, base.busy_s, base.ops)
    assert [s for _, s in named.gaps] == [s for _, s in base.gaps]
    names = {g[0] for g in named.gaps}
    assert "session.assemble" in names
    assert names <= {"session.assemble", "launch", "outside any span"}
