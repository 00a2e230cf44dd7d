"""Serving-engine tests: ticked masked EM vs serial run_em.

The acceptance bar of the serving PR (DESIGN.md §12): on a stack of
problems with deliberately mixed convergence iteration counts — the exact
case that produced BENCH_api.json's 0.45x lockstep inversion — every
request served through the continuous-batching engine must reproduce the
serial ``run_em`` result bit-for-bit in every label-visible output
(labels, segmentation, mu, sigma, em/map iteration counts; energies to
float-reduction tolerance), and admission/retirement across ticks must
never retrace the compiled tick program.
"""

import numpy as np
import pytest

import jax

from repro import api
from repro.core import synthetic
from repro.core.pmrf import em as em_mod
from repro.serving import SegmentationEngine

pytestmark = pytest.mark.slow  # full-EM runs: in the tier-1 slow bucket


def _session(**overrides):
    kwargs = dict(overseg_grid=(6, 6), capacity_bucket=2048)
    kwargs.update(overrides)
    return api.Segmenter(api.ExecutionConfig(**kwargs))


def _mixed_plans(sess, n=7, shape=(44, 44), seed=5):
    """Plans whose EM iteration counts differ (mixed-convergence premise —
    asserted, not assumed)."""
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=n, shape=shape)
    return [sess.plan(np.asarray(im)) for im in vol.images]


def _assert_matches_serial(completion, want):
    got = completion.result
    np.testing.assert_array_equal(got.region_labels, want.region_labels)
    np.testing.assert_array_equal(got.segmentation, want.segmentation)
    np.testing.assert_array_equal(got.mu, want.mu)
    np.testing.assert_array_equal(got.sigma, want.sigma)
    assert got.em_iters == want.em_iters
    assert got.map_iters == want.map_iters
    # Health status rides the same parity (DESIGN.md §14): a lane reports
    # exactly what serial run_em reports, and the completion mirrors it.
    assert got.status == want.status
    assert completion.status == want.status
    # Energies: fusion-context float noise only (DESIGN.md §12).
    np.testing.assert_allclose(
        got.total_energy, want.total_energy, rtol=1e-4
    )


def test_ticked_engine_bit_identical_on_mixed_convergence():
    sess = _session()
    plans = _mixed_plans(sess)
    serial = [sess.execute(p, seed=0) for p in plans]
    assert len({r.em_iters for r in serial}) > 1, "premise: mixed convergence"

    engine = SegmentationEngine(sess, max_batch=3, tick_iters=4)
    for rid, plan in enumerate(plans):
        engine.submit(plan, rid=rid, seed=0)
    completions = engine.run()

    assert sorted(c.rid for c in completions) == list(range(len(plans)))
    for c in completions:
        _assert_matches_serial(c, serial[c.rid])
    # more requests than slots: slots were reused across waves
    assert engine.stats()["admitted"] == len(plans)
    assert engine.ticks > 0 and engine.stats()["occupancy"] > 0.5


def test_admission_and_retirement_never_retrace():
    sess = _session()
    plans = _mixed_plans(sess, n=5)
    engine = SegmentationEngine(sess, max_batch=2, tick_iters=3)
    for rid, plan in enumerate(plans):
        engine.submit(plan, rid=rid)
    before = dict(em_mod.TRACE_COUNTS)
    completions = engine.run()
    # 5 requests / 2 slots forces several admission+retirement waves, all
    # through ONE trace of the tick program (and zero run_em traces).
    assert em_mod.TRACE_COUNTS["run_em_ticked"] == before["run_em_ticked"] + 1
    assert em_mod.TRACE_COUNTS["run_em"] == before["run_em"]
    assert len(completions) == 5
    # a second engine over the same session hits the executable cache cold-
    # trace-free (warm AOT executable, zero new traces)
    before = dict(em_mod.TRACE_COUNTS)
    engine2 = SegmentationEngine(
        sess, max_batch=2, tick_iters=3, bucket=engine.bucket
    )
    engine2.submit(plans[0], rid=0)
    engine2.run()
    assert em_mod.TRACE_COUNTS == before


def test_run_em_ticked_driver_matches_run_em_directly():
    """Driver-level identity, no engine: tick the machine to completion on
    one lane and compare the full EMResult against run_em."""
    sess = _session()
    plan = _mixed_plans(sess, n=1)[0]
    h, m, l0, mu0, s0 = sess.lane_inputs(plan)
    cfg = sess.config.em_config()
    ref = em_mod.run_em(h, m, l0, mu0, s0, cfg)

    batched = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
    hoods_b, model_b = batched(h), batched(m)
    state = batched(em_mod.init_tick_lane(l0, mu0, s0, h.n_hoods))
    ticks = 0
    total_steps = 0
    while not bool(np.asarray(state.done)[0]):
        state, steps = em_mod.run_em_ticked(
            hoods_b, model_b, state, cfg, 7
        )
        assert 1 <= int(steps) <= 7
        total_steps += int(steps)
        ticks += 1
        assert ticks <= cfg.max_em_iters * cfg.max_map_iters
    got = em_mod.tick_result(jax.tree.map(lambda x: x[0], state))
    # Early exit (partial-tick exit): the final tick stops at the
    # convergence boundary, so the executed micro-steps equal the lane's
    # total MAP iterations exactly — no riding out the tick.
    assert total_steps == int(got.map_iters)
    np.testing.assert_array_equal(np.asarray(ref.labels), np.asarray(got.labels))
    np.testing.assert_array_equal(np.asarray(ref.mu), np.asarray(got.mu))
    np.testing.assert_array_equal(np.asarray(ref.sigma), np.asarray(got.sigma))
    assert int(ref.em_iters) == int(got.em_iters)
    assert int(ref.map_iters) == int(got.map_iters)
    np.testing.assert_allclose(
        np.asarray(ref.hood_energy), np.asarray(got.hood_energy), rtol=1e-4
    )


def test_ticked_vmap_path_matches_serial_faithful_mode():
    """The non-static modes go through the vmapped lane step — same
    bit-identity contract."""
    sess = _session(mode="faithful")
    plan = _mixed_plans(sess, n=1, seed=9)[0]
    want = sess.execute(plan, seed=0)

    engine = SegmentationEngine(sess, max_batch=2, tick_iters=4)
    engine.submit(plan, rid=0, seed=0)
    (completion,) = engine.run()
    _assert_matches_serial(completion, want)


def test_deadline_ordered_admission():
    sess = _session()
    plans = _mixed_plans(sess, n=3)
    # one slot: admission order == completion order
    engine = SegmentationEngine(sess, max_batch=1, tick_iters=8)
    engine.submit(plans[0], rid=0, deadline_s=30.0)
    engine.submit(plans[1], rid=1)                 # no deadline: last
    engine.submit(plans[2], rid=2, deadline_s=1.0)  # tightest: first
    completions = engine.run()
    assert [c.rid for c in completions] == [2, 0, 1]
    # honest latency split (DESIGN.md §17): queue + residence == latency,
    # and the deprecated service_s alias still reads as residence
    for c in completions:
        assert c.latency_s == pytest.approx(c.queue_s + c.residence_s, abs=1e-3)
        assert c.service_s == c.residence_s
        assert c.ticks_resident >= 1


def test_admission_is_deterministic_with_all_none_deadlines():
    """Equal deadline keys (here: every deadline None) tie-break by rid —
    admission order is a pure function of the submitted rids, not of heap
    internals or submission order."""
    sess = _session()
    plans = _mixed_plans(sess, n=3)
    for submit_order in ([2, 0, 1], [1, 2, 0], [0, 1, 2]):
        engine = SegmentationEngine(sess, max_batch=1, tick_iters=8)
        for rid in submit_order:
            engine.submit(plans[rid], rid=rid)
        completions = engine.run()
        assert [c.rid for c in completions] == [0, 1, 2], submit_order
    # non-int rids cannot enter the heap (they would break the tie-break)
    engine = SegmentationEngine(sess, max_batch=1, tick_iters=8)
    with pytest.raises(api.RequestError, match="rid must be an int"):
        engine.submit(plans[0], rid="abc")


def test_priority_classes_order_admission_before_deadlines():
    sess = _session()
    plans = _mixed_plans(sess, n=3)
    engine = SegmentationEngine(sess, max_batch=1, tick_iters=8)
    engine.submit(plans[0], rid=0, priority=1, deadline_s=0.5)  # background
    engine.submit(plans[1], rid=1)                              # default
    engine.submit(plans[2], rid=2, priority=-1)                 # urgent
    completions = engine.run()
    assert [c.rid for c in completions] == [2, 1, 0]


def test_adaptive_tick_cache_per_size_no_retrace_no_alias():
    """``ExecutableKey.tick_iters`` under adaptive ticking (DESIGN.md §17):
    pool bring-up traces each ladder size exactly once, tick-size switches
    hit the LRU warm (zero new traces — regardless of how many switches
    happen), distinct sizes get distinct cache keys (never aliased), and
    results stay bitwise serial-identical under any tick-size schedule."""
    # The trace counts below assume a cold jit cache: another test file in
    # the same process may already have traced these ticked programs.
    jax.clear_caches()
    sess = _session()
    plans = _mixed_plans(sess, n=5)
    serial = [sess.execute(p, seed=0) for p in plans]
    ladder = (1, 2, 4)
    before = dict(em_mod.TRACE_COUNTS)
    engine = SegmentationEngine(
        sess, max_batch=2, tick_iters="auto", tick_ladder=ladder,
        tick_hysteresis=1,
    )
    for rid, plan in enumerate(plans):
        # tight deadlines drive the policy's deadline clamp to the
        # smallest ladder size -> guaranteed switches to exercise
        engine.submit(plan, rid=rid, seed=0, deadline_s=0.001)
    completions = engine.run()
    assert len(completions) == len(plans)
    for c in completions:
        _assert_matches_serial(c, serial[c.rid])
    assert len(engine.tick_switches) >= 1
    # the expired deadlines clamp the policy to the smallest size while
    # lanes are live (a switch down to ladder[0] must be recorded); once
    # the pool drains there are no live deadlines, so the policy is free
    # to move back up — the final size is unconstrained beyond the ladder
    assert any(to == ladder[0] for _, _, to in engine.tick_switches)
    assert engine.tick_iters in ladder
    # each distinct size hit the trace path exactly once, at bring-up;
    # every switch afterwards was a warm cache hit
    assert (
        em_mod.TRACE_COUNTS["run_em_ticked"]
        == before["run_em_ticked"] + len(ladder)
    )
    assert em_mod.TRACE_COUNTS["run_em"] == before["run_em"]
    # one ExecutableKey per size at the pool's batch — sizes never alias
    keys = [
        k for k in sess.cache_keys
        if k.tick_iters is not None and k.batch == 2
    ]
    assert {k.tick_iters for k in keys} == set(ladder)
    assert len(keys) == len(ladder)
    st = engine.stats()
    assert st["adaptive"] and st["tick_cost"]["model_per_step_s"] > 0
    assert st["steps_saved_early_exit"] >= 0

    # a second adaptive engine on the same session: zero new traces for
    # the whole ladder (warm AOT executables)
    before = dict(em_mod.TRACE_COUNTS)
    engine2 = SegmentationEngine(
        sess, max_batch=2, tick_iters="auto", tick_ladder=ladder,
        bucket=engine.bucket,
    )
    engine2.submit(plans[0], rid=0, seed=0)
    (c2,) = engine2.run()
    assert em_mod.TRACE_COUNTS == before
    _assert_matches_serial(c2, serial[0])


def test_mixed_k_requests_share_one_pool():
    """DESIGN.md §13: a K=3 pool serves K=2 and K=3 requests together.
    Smaller-K plans are label-padded with inert sentinel labels, so each
    lane's real labels take the bitwise natural-K trajectory — the K=2
    request must reproduce a *K=2 session's* serial result exactly."""
    vol2 = synthetic.make_synthetic_volume(seed=5, n_slices=1, shape=(44, 44))
    vol3 = synthetic.make_kary_volume(
        seed=5, n_slices=1, shape=(44, 44), n_phases=3
    )
    sess2 = _session(init="quantile")
    sess3 = _session(n_labels=3, init="quantile")
    plan2 = sess2.plan(np.asarray(vol2.images[0]))
    plan3 = sess3.plan(np.asarray(vol3.images[0]))
    want2 = sess2.execute(plan2, seed=0)       # natural-K serial references
    want3 = sess3.execute(plan3, seed=0)

    engine = SegmentationEngine(sess3, max_batch=2, tick_iters=4)
    engine.submit(plan2, rid=2, seed=0)        # K=2 request in the K=3 pool
    engine.submit(plan3, rid=3, seed=0)
    completions = {c.rid: c for c in engine.run()}
    assert sorted(completions) == [2, 3]

    got2 = completions[2].result
    np.testing.assert_array_equal(got2.region_labels, want2.region_labels)
    np.testing.assert_array_equal(got2.segmentation, want2.segmentation)
    assert got2.em_iters == want2.em_iters
    assert got2.map_iters == want2.map_iters
    # real labels' parameters are bitwise the K=2 run; the inert padded
    # label re-seeds to the sentinel every M-step
    np.testing.assert_array_equal(got2.mu[:2], want2.mu)
    np.testing.assert_array_equal(got2.sigma[:2], want2.sigma)
    from repro.core.pmrf import energy as energy_mod

    assert got2.mu[2] == energy_mod.INERT_MU
    _assert_matches_serial(completions[3], want3)

    # larger-K requests need a wider pool: loud failure
    engine2 = SegmentationEngine(sess2, max_batch=1)
    with pytest.raises(ValueError, match="wider pool"):
        engine2.submit(plan3)


def test_engine_rejects_oversized_and_sharded():
    sess = _session()
    plans = _mixed_plans(sess, n=1)
    engine = SegmentationEngine(sess, max_batch=1, bucket=api.BucketKey(64, 8, 8))
    with pytest.raises(ValueError, match="exceeds the engine's fixed pool"):
        engine.submit(plans[0])
    with pytest.raises(ValueError, match="single-device"):
        SegmentationEngine(api.ExecutionConfig(shards=2))
    with pytest.raises(ValueError, match="single-device"):
        api.Segmenter(api.ExecutionConfig(shards=2)).compile_ticked(
            api.BucketKey(64, 8, 8), batch=2
        )
