"""The program's spans (``repro.obs``): nesting, self time, compile
attribution, the profiler's recording, LEDGER snapshots, and the spans the
session, the serving engine and the DPP profiler emit."""

import math
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.analysis import budget
from repro.core import dpp, synthetic
from repro.serving import SegmentationEngine

PLAN_STAGES = ("plan.slic", "plan.graph", "plan.cliques", "plan.hoods", "plan.model")
SESSION_SPANS = ("session.compile", "session.pad", "session.launch", "session.wait",
                 "session.assemble")


def _fresh_program():
    """A jitted function no earlier call compiled: its constant is new."""
    c = float(time.time_ns() % 1_000_003) + 0.5
    return jax.jit(lambda x: x * c + 1.0)


def _images(n, size=40, seed=3):
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=n, shape=(size, size))
    return [np.asarray(im) for im in vol.images]


@pytest.fixture(scope="module")
def sess():
    return api.Segmenter(api.ExecutionConfig(overseg_grid=(5, 5), capacity_bucket=2048))


def test_nesting_and_self_time():
    with obs.recording() as rec:
        with obs.span("outer", req=7) as outer:
            time.sleep(0.01)
            with obs.span("inner") as inner:
                time.sleep(0.02)
            with obs.span("inner"):
                pass
    outer_r, inner_r, inner2_r = rec.records
    assert [r.name for r in rec.records] == ["outer", "inner", "inner"]
    assert inner_r.parent is outer_r and inner2_r.parent is outer_r and outer_r.parent is None
    assert inner_r.req == 7 and inner2_r.req == 7  # children take the parent's request
    assert outer_r.seconds == outer.seconds and inner_r.seconds == inner.seconds
    own = rec.self_seconds()
    assert own[id(outer_r)] == pytest.approx(
        outer_r.seconds - inner_r.seconds - inner2_r.seconds)
    assert own[id(outer_r)] >= 0.009 and own[id(inner_r)] >= 0.019
    rows = {row["span"]: row for row in rec.table()}
    assert rows["inner"]["n"] == 2 and rows["outer"]["n"] == 1
    assert rows["outer"]["self_s"] == pytest.approx(own[id(outer_r)])


def test_compile_counted_on_innermost_span():
    x = jnp.arange(13.0)
    with obs.recording() as rec:
        with obs.span("plan"):
            with obs.span("plan.hoods"):
                _fresh_program()(x).block_until_ready()
            with obs.span("plan.graph"):
                pass
    plan, hoods, graph = rec.records
    assert hoods.compiles >= 1 and hoods.compile_s > 0
    assert plan.compiles == 0 and plan.compile_s == 0.0
    assert graph.compiles == 0
    rows = {row["span"]: row for row in rec.table()}
    assert rows["plan.hoods"]["compiles"] == hoods.compiles


def test_no_records_without_a_recording():
    with obs.span("alone") as sp:
        _fresh_program()(jnp.arange(5.0)).block_until_ready()
    assert sp.record is None
    assert sp.seconds > 0 and not math.isnan(sp.seconds)
    with obs.recording() as rec:
        pass
    assert rec.records == []
    # A recording keeps only what opened inside it.
    with obs.span("before"):
        with obs.recording() as rec:
            with obs.span("inside"):
                pass
    assert [r.name for r in rec.records] == ["inside"]
    assert rec.records[0].parent is None


def test_nested_recordings_share_records():
    with obs.recording() as outer:
        with obs.span("a"):
            pass
        with obs.recording() as inner:
            with obs.span("b"):
                pass
    assert [r.name for r in outer.records] == ["a", "b"]
    assert [r.name for r in inner.records] == ["b"]
    assert inner.records[0] is outer.records[1]


def test_spans_from_many_threads():
    """Each thread nests its own spans; one recording keeps them all."""
    n_threads, n_spans = 16, 200

    def work(k):
        for _ in range(n_spans):
            with obs.span("outer", req=k):
                with obs.span("inner"):
                    pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.recording() as rec:
            threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(rec.records) == 2 * n_threads * n_spans
    for r in rec.named("inner"):
        assert r.parent.name == "outer" and r.parent.req == r.req
        assert r.parent.start <= r.start and r.end <= r.parent.end


def test_ledger_deltas_in_the_snapshot():
    budget.LEDGER.bump("compile", "warm_hit")
    with obs.recording() as rec:
        budget.LEDGER.bump("compile", "warm_hit", 2)
        budget.LEDGER.bump("serve", "ticks")
        assert rec.ledger_delta() == {"compile": {"warm_hit": 2}, "serve": {"ticks": 1}}
    budget.LEDGER.bump("compile", "warm_hit")  # after the close: not in the delta
    assert rec.ledger_delta() == {"compile": {"warm_hit": 2}, "serve": {"ticks": 1}}
    assert rec.ledger_open["compile"]["warm_hit"] == 1
    assert rec.ledger_close["compile"]["warm_hit"] == 3
    assert "ledger compile: warm_hit +2" in rec.format_table()


def test_profiler_trace_is_a_recording(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("traced.outer"):
            with obs.span("traced.inner"):
                _fresh_program()(jnp.arange(3.0)).block_until_ready()
    rec = obs.traced()
    assert rec is not None
    names = [r.name for r in rec.records]
    assert names[-2:] == ["traced.outer", "traced.inner"]
    assert rec.records[-1].compiles >= 1
    with obs.span("after"):  # the first span after the trace closes its recording
        pass
    assert rec.ledger_close is not None
    assert "after" not in [r.name for r in rec.records]


def test_session_emits_plan_and_session_spans(sess):
    a, b, c = _images(3)
    with obs.recording() as rec:
        plan = sess.plan(a)
        res = sess.execute(plan)
        pb = sess.plan(b)
        sess.submit(pb)
        sess.drain()                       # one request: the execute path
        p1, p2 = sess.plan(b), sess.plan(c)
        joint = api.BucketKey(*(max(x, y) for x, y in zip(p1.bucket, p2.bucket)))
        sess.submit(p1, bucket=joint)
        sess.submit(p2, bucket=joint)
        sess.drain()                       # two requests: one vmapped launch
    names = [r.name for r in rec.records]
    for name in ("plan",) + PLAN_STAGES:
        assert names.count(name) == 4, name
    assert not [n for n in names if n.startswith("plan.") and n not in PLAN_STAGES]
    for name in SESSION_SPANS:
        assert names.count(name) == 3, name
    assert names.count("session.execute") == 3
    # Every stage sits under its parent, tagged with the request's plan id.
    execs = rec.named("session.execute")
    assert [r.req for r in execs] == [plan.id, pb.id, (p1.id, p2.id)]
    for r in rec.records:
        if r.name.startswith("plan."):
            assert r.parent.name == "plan" and r.req == r.parent.req
        if r.name.startswith("session.") and r.name != "session.execute":
            assert r.parent.name == "session.execute" and r.req == r.parent.req
    assert res.ok


def test_timing_fields_read_off_their_spans(sess):
    (image,) = _images(1, seed=11)
    with obs.recording() as rec:
        plan = sess.plan(image)
        sess.clear_cache()
        res = sess.execute(plan)
    whole, = rec.named("plan")
    assert plan.init_seconds == whole.seconds
    launch, = rec.named("session.launch")
    wait, = rec.named("session.wait")
    assert res.optimize_seconds == launch.seconds + wait.seconds
    assert res.init_seconds == plan.init_seconds
    compile_, = rec.named("session.compile")  # a miss: the cache was cleared
    exe = sess.compile(plan)
    assert exe.compile_seconds == compile_.seconds


def test_engine_phases_are_spans(sess):
    plans = [sess.plan(im) for im in _images(3, seed=5)]
    engine = SegmentationEngine(sess, max_batch=2, tick_iters=4)
    for rid, plan in enumerate(plans):
        engine.submit(plan, rid=rid)
    with obs.recording() as rec:
        completions = engine.run()
    assert len(completions) == 3
    phase_s = engine.stats()["tick_cost"]["phase_s"]
    assert list(phase_s) == ["admit", "advance", "sync", "retire"]
    for phase in phase_s:
        spans = rec.named(f"serve.{phase}")
        assert len(spans) == engine.ticks > 0
        assert engine._phase_s[phase] == pytest.approx(
            sum(r.seconds for r in spans), abs=1e-12)


def test_dpp_profiled_is_a_view_over_spans():
    x = jnp.arange(8, dtype=jnp.float32)
    assert dpp._views == 0
    with obs.recording() as outer:
        with dpp.profiled() as prof:
            assert dpp._views == 1
            dpp.scan_(x)
            dpp.reduce_(x)
    assert dpp._views == 0
    assert prof.counts() == {"Scan": 1, "Reduce": 1}
    assert [r.name for r in outer.records] == ["dpp.Scan", "dpp.Reduce"]
    assert prof.totals()["Scan"] == outer.records[0].seconds
    dpp.reduce_(x)  # outside the view: no span, no record
    assert len(outer.records) == 2


def test_segment_cli_prints_span_table():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.segment", "--slices", "1", "--size", "40",
         "--grid", "5", "--spans"],
        capture_output=True, text=True, timeout=600, check=True,
    )
    lines = out.stderr.splitlines()
    start = next(i for i, line in enumerate(lines) if line.split()[:1] == ["span"])
    assert lines[start].split() == ["span", "n", "total_s", "self_s", "compiles", "compile_s"]
    names = {line.split()[0] for line in lines[start + 1:] if not line.startswith("ledger")}
    assert {"plan", *PLAN_STAGES, "session.execute", *SESSION_SPANS} <= names
