"""Tests for the session API (repro.api, DESIGN.md §10).

Covers the acceptance bar of the api_redesign PR: a second same-bucket
``execute`` performs zero new traces (trace counting via
``em.TRACE_COUNTS``, the same helper test_fused_map.py uses), 8 same-bucket
``submit``s compile once and match 8 serial ``segment_image`` calls
bit-identically, different buckets miss, eviction respects the configured
max size, and the legacy surfaces (``segment_image``/``segment_volume``)
warn but keep working.  The pre-registry ``use_pallas=`` boolean completed
its one-release deprecation window and is rejected outright.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import session as session_mod
from repro.core import synthetic
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import pipeline
from repro.kernels import ops as kops


def _images(n=2, shape=(44, 44), seed=3):
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=n, shape=shape)
    return [np.asarray(im) for im in vol.images]


def _fresh(config=None):
    """Cold world: no jit caches, no module sessions, a fresh Segmenter."""
    jax.clear_caches()
    api.reset_sessions()
    return api.Segmenter(config or api.ExecutionConfig(overseg_grid=(6, 6)))


# ---------------------------------------------------------------------------
# ExecutionConfig: validation + resolution order
# ---------------------------------------------------------------------------


def test_config_validates_knobs():
    with pytest.raises(ValueError, match="mode"):
        api.ExecutionConfig(mode="bogus")
    with pytest.raises(ValueError, match="backend"):
        api.ExecutionConfig(backend="cuda")
    with pytest.raises(ValueError, match="init"):
        api.ExecutionConfig(init="zeros")
    with pytest.raises(ValueError, match="bucket"):
        api.ExecutionConfig(capacity_bucket=0)
    with pytest.raises(ValueError, match="max_cached"):
        api.ExecutionConfig(max_cached_executables=0)


def test_config_resolution_order(monkeypatch):
    monkeypatch.delenv(kops.ENV_VAR, raising=False)
    kops.set_default_backend(None)
    auto = "pallas-tpu" if jax.default_backend() == "tpu" else "xla"
    # step 4: platform auto-detection
    assert api.ExecutionConfig().resolved_backend() == auto
    # step 2: env var beats auto
    monkeypatch.setenv(kops.ENV_VAR, "pallas-interpret")
    assert api.ExecutionConfig().resolved_backend() == "pallas-interpret"
    # step 1: explicit field beats env
    assert api.ExecutionConfig(backend="xla").resolved_backend() == "xla"
    # em_config pins the concrete name (never "auto")
    assert api.ExecutionConfig().em_config().backend == "pallas-interpret"


def test_config_is_hashable_session_key():
    a = api.ExecutionConfig(overseg_grid=[6, 6])  # list coerced to tuple
    b = api.ExecutionConfig(overseg_grid=(6, 6))
    assert a == b and hash(a) == hash(b)
    api.reset_sessions()
    assert api.session_for(a) is api.session_for(b)
    assert api.session_for(a) is not api.session_for(b.with_(mode="faithful"))


# ---------------------------------------------------------------------------
# executable cache: hit / miss / eviction
# ---------------------------------------------------------------------------


def test_second_same_bucket_execute_is_zero_trace():
    seg = _fresh()
    img_a, img_b = _images(2)
    # Pin the oversegmentation: the graph (and thus the data-dependent hood
    # capacity) is a function of the label map alone, so both plans land in
    # the same bucket by construction — SLIC pixel flips near a bucket
    # boundary otherwise make this premise flaky.
    overseg = np.repeat(np.repeat(np.arange(36).reshape(6, 6), 8, 0), 8, 1)[:44, :44]
    plan_a = seg.plan(img_a, oversegmentation=overseg)
    plan_b = seg.plan(img_b, oversegmentation=overseg)
    assert plan_a.bucket == plan_b.bucket  # coarse buckets: same compile unit

    res_a = seg.execute(plan_a)
    assert seg.stats.misses == 1
    before = dict(em_mod.TRACE_COUNTS)
    res_b = seg.execute(plan_b)
    assert em_mod.TRACE_COUNTS == before, "warm-cache execute must not trace"
    assert seg.stats.hits == 1
    assert np.isfinite(res_a.total_energy) and np.isfinite(res_b.total_energy)
    assert res_b.segmentation.shape == img_b.shape


def test_different_bucket_misses():
    cfg = api.ExecutionConfig(
        overseg_grid=(6, 6), capacity_bucket=1, segment_bucket=1
    )
    seg = _fresh(cfg)
    vol_a = synthetic.make_synthetic_volume(seed=0, n_slices=1, shape=(40, 40))
    vol_b = synthetic.make_synthetic_volume(seed=1, n_slices=1, shape=(64, 64))
    plan_a = seg.plan(np.asarray(vol_a.images[0]))
    plan_b = seg.plan(np.asarray(vol_b.images[0]))
    assert plan_a.bucket != plan_b.bucket  # exact buckets: distinct units

    seg.execute(plan_a)
    before = dict(em_mod.TRACE_COUNTS)
    seg.execute(plan_b)
    assert em_mod.TRACE_COUNTS["run_em"] == before["run_em"] + 1
    assert seg.stats.misses == 2 and seg.stats.hits == 0
    assert len(seg.cache_keys) == 2


def test_cache_eviction_respects_max_size():
    cfg = api.ExecutionConfig(
        overseg_grid=(6, 6), capacity_bucket=1, segment_bucket=1,
        max_cached_executables=1,
    )
    seg = _fresh(cfg)
    vol_a = synthetic.make_synthetic_volume(seed=0, n_slices=1, shape=(40, 40))
    vol_b = synthetic.make_synthetic_volume(seed=1, n_slices=1, shape=(64, 64))
    plan_a = seg.plan(np.asarray(vol_a.images[0]))
    plan_b = seg.plan(np.asarray(vol_b.images[0]))
    assert plan_a.bucket != plan_b.bucket

    exe_a = seg.compile(plan_a)
    seg.compile(plan_b)  # evicts a (LRU, max size 1)
    assert seg.stats.evictions == 1
    assert len(seg.cache_keys) == 1
    assert seg.cache_keys[0].capacity == plan_b.bucket.capacity
    # a is gone: compiling it again is a miss, not a hit
    seg.compile(plan_a)
    assert seg.stats.misses == 3
    assert exe_a.key.backend != "auto"  # keys pin the resolved backend


def test_compile_accepts_bucket_key_without_data():
    # compile() needs only shapes — a bare BucketKey, no plan/arrays.
    seg = _fresh()
    img = _images(1)[0]
    bucket = seg.plan(img).bucket
    seg2 = api.Segmenter(seg.config)
    exe = seg2.compile(api.BucketKey(*bucket))
    assert seg2.stats.misses == 1
    assert exe.key.batch is None and exe.compile_seconds > 0.0


# ---------------------------------------------------------------------------
# micro-batching: submit / drain
# ---------------------------------------------------------------------------


def test_submit_8_compiles_once_and_matches_serial():
    # Coarse capacity bucket: slice capacities are data-dependent and can
    # straddle a 256-lane boundary, which would (correctly) split the batch
    # — this test is about the one-bucket path.
    seg = _fresh(api.ExecutionConfig(overseg_grid=(6, 6), capacity_bucket=2048))
    imgs = _images(8, shape=(44, 44), seed=5)
    plans = [seg.plan(img) for img in imgs]
    assert len({p.bucket for p in plans}) == 1, "test premise: one bucket"

    before = dict(em_mod.TRACE_COUNTS)
    tickets = [seg.submit(p, seed=0) for p in plans]
    assert seg.pending() == 8
    batched = seg.drain()
    assert seg.pending() == 0
    assert em_mod.TRACE_COUNTS["run_em_batched"] == before["run_em_batched"] + 1
    assert em_mod.TRACE_COUNTS["run_em"] <= before["run_em"] + 1
    assert seg.stats.misses == 1  # ONE batch-8 executable for all 8 requests
    assert tickets == list(range(8)) and len(batched) == 8

    # bit-identical to 8 serial segment_image calls (the legacy one-shots)
    for img, got in zip(imgs, batched):
        with pytest.warns(DeprecationWarning):
            want = pipeline.segment_image(img, overseg_grid=(6, 6), seed=0)
        np.testing.assert_array_equal(got.region_labels, want.region_labels)
        np.testing.assert_array_equal(got.segmentation, want.segmentation)
        np.testing.assert_array_equal(got.mu, want.mu)
        np.testing.assert_array_equal(got.sigma, want.sigma)
        assert got.em_iters == want.em_iters


def test_drain_groups_mixed_buckets():
    # capacity_bucket=2048: slice capacities (~1k) never straddle a bucket
    # boundary, so the two (40, 40) plans share a bucket deterministically.
    seg = _fresh(api.ExecutionConfig(overseg_grid=(6, 6), capacity_bucket=2048))
    vol_a = synthetic.make_synthetic_volume(seed=0, n_slices=2, shape=(40, 40))
    vol_b = synthetic.make_synthetic_volume(seed=1, n_slices=1, shape=(64, 64))
    pa1, pa2 = (seg.plan(np.asarray(im)) for im in vol_a.images)
    # A custom oversegmentation with ~7x the regions lands in a different
    # n_regions bucket under the same session config.
    overseg = np.repeat(np.repeat(np.arange(256).reshape(16, 16), 4, 0), 4, 1)
    pb = seg.plan(np.asarray(vol_b.images[0]), oversegmentation=overseg)
    assert pa1.bucket == pa2.bucket != pb.bucket

    seg.submit(pa1)
    seg.submit(pb)
    seg.submit(pa2)
    results = seg.drain()
    assert len(results) == 3
    # order preserved across groups: results[i] belongs to submission i
    assert results[0].segmentation.shape == (40, 40)
    assert results[1].segmentation.shape == (64, 64)
    assert results[2].segmentation.shape == (40, 40)
    # one batch-2 executable + one single executable
    assert {k.batch for k in seg.cache_keys} == {None, 2}


def test_drain_empty_is_noop():
    seg = _fresh()
    assert seg.drain() == []


def test_drain_failure_requeues_unprocessed():
    seg = _fresh()
    img = _images(1)[0]
    plan = seg.plan(img)
    bad = api.BucketKey(1, 1, 1)  # smaller than the plan's hoods: pad raises
    seg.submit(plan, bucket=bad)
    seg.submit(plan)
    with pytest.raises(ValueError, match="smaller than hoods"):
        seg.drain()
    # the failing group AND the never-reached group are both back in queue
    assert seg.pending() == 2
    # after dropping the poisoned request, the healthy one still drains
    seg._pending.pop(0)
    assert len(seg.drain()) == 1


# ---------------------------------------------------------------------------
# segment_stack(batch="auto"): the batching rule + warm reuse
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "capacities, platform, batched",
    [
        ([100, 120], "cpu", False),    # XLA:CPU runs vmapped lanes serially
        ([100, 120], "tpu", True),     # accelerator, capacities within 2x
        ([100, 300], "tpu", False),    # accelerator, spread over 2x
        ([100], "tpu", False),         # one plan
    ],
)
def test_escape_hatch_restores_legacy_heuristic(capacities, platform, batched):
    assert session_mod._batch_pays(capacities, platform) is batched


def test_autotuned_segment_stack_reuses_warm_executables():
    seg = _fresh()
    imgs = _images(n=3)
    res_a, _ = seg.segment_stack(imgs, batch="auto")
    misses = seg.stats.misses
    before = dict(em_mod.TRACE_COUNTS)
    res_b, _ = seg.segment_stack(imgs, batch="auto")
    assert em_mod.TRACE_COUNTS == before, \
        "a second segment_stack must reuse the warm executable cache, not retrace"
    assert seg.stats.misses == misses
    # on CPU the rule runs the stack serially: the same results as "never"
    res_loop, _ = seg.segment_stack(imgs, batch="never")
    assert seg.stats.misses == misses
    for a, b, c in zip(res_a, res_b, res_loop):
        assert (np.asarray(a.segmentation) == np.asarray(b.segmentation)).all()
        assert (np.asarray(a.segmentation) == np.asarray(c.segmentation)).all()


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------


def test_segment_image_shim_warns_and_matches_session():
    img = _images(1)[0]
    api.reset_sessions()
    with pytest.warns(DeprecationWarning, match="segment_image is deprecated"):
        legacy = pipeline.segment_image(img, overseg_grid=(6, 6), seed=0)
    sess = api.session_for(api.ExecutionConfig(overseg_grid=(6, 6)))
    modern = sess.segment(img, seed=0)
    np.testing.assert_array_equal(legacy.segmentation, modern.segmentation)
    np.testing.assert_array_equal(legacy.region_labels, modern.region_labels)


def test_segment_volume_shim_warns_and_validates():
    with pytest.warns(DeprecationWarning, match="segment_volume is deprecated"):
        with pytest.raises(ValueError, match="batch"):
            pipeline.segment_volume([np.zeros((8, 8))], batch="maybe")


def test_use_pallas_kwarg_removed():
    # The one-release warning shim shipped its release: use_pallas= is no
    # longer a recognized kwarg anywhere in the dispatch layer.
    vals = jnp.asarray(np.arange(12, dtype=np.float32))
    segs = jnp.asarray(np.arange(12, dtype=np.int32) % 3)
    with pytest.raises(TypeError, match="use_pallas"):
        kops.segment_reduce(vals, segs, 3, "add", use_pallas=False)
    with pytest.raises(TypeError, match="use_pallas"):
        kops.flash_attention(
            jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 1, 8, 4)),
            jnp.zeros((1, 1, 8, 4)), use_pallas=True,
        )
    # the explicit backend= spelling is the supported surface
    out = kops.segment_reduce(vals, segs, 3, "add", backend="xla")
    assert out.shape == (3,)
