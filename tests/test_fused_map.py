"""Tests for the kernel-dispatch layer, the fused MAP-step path, and the
batched multi-slice ``segment_volume``.

Covers the acceptance bar of the fusion PR: static-pallas labels identical
to static on CPU (interpret backend), strictly fewer scatter launches per
MAP iteration (jaxpr op count), and an 8-slice stack compiling ``run_em``
exactly once.
"""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _scatter_map_step import scatter_map_step
from repro import api
from repro.core import dpp, synthetic
from repro.core.pmrf import EMConfig, initialize, run_em
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as energy_mod
from repro.core.pmrf import pipeline
from repro.kernels import ops as kops
from repro.kernels import ref


def _problem(seed=3, shape=(48, 48), grid=(6, 6)):
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=1, shape=shape)
    return initialize(np.asarray(vol.images[0]), overseg_grid=grid)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------


def test_backend_auto_detection(monkeypatch):
    # Neutralize ambient routing (the CI matrix runs the whole suite under
    # REPRO_KERNEL_BACKEND=pallas-interpret) — this test is about step 4 of
    # the resolution order.
    monkeypatch.delenv(kops.ENV_VAR, raising=False)
    kops.set_default_backend(None)
    want = "pallas-tpu" if jax.default_backend() == "tpu" else "xla"
    assert kops.resolve_backend(None) == want
    assert kops.resolve_backend("auto") == want


def test_backend_explicit_and_alias():
    assert kops.resolve_backend("xla") == "xla"
    assert kops.resolve_backend("pallas-interpret") == "pallas-interpret"
    want = "pallas-tpu" if jax.default_backend() == "tpu" else "pallas-interpret"
    assert kops.resolve_backend("pallas") == want
    with pytest.raises(ValueError):
        kops.resolve_backend("cuda")


def test_backend_env_and_override(monkeypatch):
    monkeypatch.setenv(kops.ENV_VAR, "pallas-interpret")
    assert kops.resolve_backend(None) == "pallas-interpret"
    monkeypatch.delenv(kops.ENV_VAR)
    kops.set_default_backend("pallas-interpret")
    try:
        assert kops.resolve_backend("auto") == "pallas-interpret"
        # explicit argument still wins
        assert kops.resolve_backend("xla") == "xla"
    finally:
        kops.set_default_backend(None)
    with pytest.raises(ValueError):
        kops.set_default_backend("not-a-backend")


def test_registry_lists_ops():
    ops = kops.registered_ops()
    for name in ("segment_reduce", "mrf_min_energy", "fused_map_step", "flash_attention"):
        assert name in ops


def test_reduce_by_key_backend_routing():
    rng = np.random.RandomState(0)
    vals = jnp.asarray(rng.randn(700), jnp.float32)
    segs = jnp.asarray(rng.randint(0, 13, 700), jnp.int32)
    base = np.asarray(dpp.reduce_by_key(segs, vals, 13, op="add"))
    via_pallas = np.asarray(
        dpp.reduce_by_key(segs, vals, 13, op="add", backend="pallas-interpret")
    )
    np.testing.assert_allclose(via_pallas, base, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused MAP-step kernel
# ---------------------------------------------------------------------------


def test_fused_map_step_matches_unfused_composition():
    prob = _problem(seed=5)
    hoods, model = prob.hoods, prob.model
    labels, mu, sigma = em_mod.init_params(jax.random.PRNGKey(1), prob.graph.n_regions)

    # Unfused static-mode composition
    energies = energy_mod.label_energies(hoods, model, labels, mu, sigma)
    want_min, want_arg = energy_mod.min_energies_static(energies)
    want_hood = energy_mod.hood_energy_sums(hoods, want_min)
    want_labels = energy_mod.vote_labels(hoods, want_arg, hoods.n_regions, 2)

    ctx = energy_mod.make_static_context(hoods, model, backend="pallas-interpret")
    got_labels, got_hood = energy_mod.map_step_fused(
        hoods, model, ctx, labels, mu, sigma, backend="pallas-interpret"
    )
    np.testing.assert_array_equal(np.asarray(got_labels), np.asarray(want_labels))
    np.testing.assert_allclose(
        np.asarray(got_hood), np.asarray(want_hood), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("n_labels", [2, 3, 5])
def test_fused_map_step_pallas_matches_ref_oracle(n_labels):
    rng = np.random.RandomState(7)
    n, n_hoods, n_vert = 900, 37, 61
    y = jnp.asarray(rng.uniform(0, 255, n), jnp.float32)
    valid = jnp.asarray(rng.rand(n) < 0.9, jnp.float32)
    w = jnp.asarray(rng.uniform(0, 2, n), jnp.float32) * valid
    nall = jnp.asarray(rng.randint(2, 20, n), jnp.float32)
    x = rng.randint(0, n_labels, n)
    # per-(element, label) hood counts consistent with nall: a random
    # composition of each element's neighborhood size over the K labels
    cnt = rng.multinomial(1, np.ones(n_labels) / n_labels, size=n).T * np.asarray(nall)
    cnt_e = jnp.asarray(cnt, jnp.float32)
    xf = jnp.asarray(x, jnp.float32) * valid
    hood_id = jnp.asarray(rng.randint(0, n_hoods, n), jnp.int32)
    vertex = jnp.asarray(rng.randint(0, n_vert, n), jnp.int32)
    mu = jnp.asarray(np.linspace(60.0, 200.0, n_labels), jnp.float32)
    sigma = jnp.asarray(np.linspace(25.0, 35.0, n_labels), jnp.float32)

    args = (y, w, cnt_e, nall, xf, valid, hood_id, vertex, mu, sigma, 0.75)
    kw = dict(n_hoods=n_hoods, n_vertices=n_vert)
    want = ref.fused_map_step(*args, **kw)
    got = kops.fused_map_step(*args, backend="pallas-interpret", **kw)
    assert got[3].shape == (n_labels, n_vert)
    for g, w_, tol in zip(got, want, (1e-6, 0, 1e-4, 0)):
        if tol:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w_), rtol=1e-5, atol=tol)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


# ---------------------------------------------------------------------------
# mode equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 11])
def test_all_modes_produce_identical_labels(seed):
    prob = _problem(seed=seed)
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(7), prob.graph.n_regions
    )
    results = {}
    for mode, backend in (
        ("faithful", "auto"),
        ("static", "auto"),
        ("static", "pallas-interpret"),  # backend must route in static too
        ("static-pallas", "pallas-interpret"),
        ("static-pallas", "xla"),
    ):
        cfg = EMConfig(mode=mode, backend=backend)
        results[(mode, backend)] = run_em(
            prob.hoods, prob.model, labels0, mu0, sigma0, cfg
        )
    base = results[("static", "auto")]
    for key, res in results.items():
        np.testing.assert_array_equal(
            np.asarray(res.labels), np.asarray(base.labels), err_msg=str(key)
        )
        np.testing.assert_allclose(
            np.asarray(res.mu), np.asarray(base.mu), rtol=1e-4, err_msg=str(key)
        )
        np.testing.assert_allclose(
            float(res.total_energy), float(base.total_energy), rtol=1e-4,
            err_msg=str(key),
        )
        assert int(res.em_iters) == int(base.em_iters), key


def test_unknown_mode_raises():
    prob = _problem()
    labels0, mu0, sigma0 = em_mod.init_params(jax.random.PRNGKey(0), prob.graph.n_regions)
    with pytest.raises(ValueError, match="unknown mode"):
        run_em(prob.hoods, prob.model, labels0, mu0, sigma0, EMConfig(mode="bogus"))


# ---------------------------------------------------------------------------
# launch count: the fused path must issue strictly fewer scatter/segment
# launches per MAP iteration than the unfused static mode
# ---------------------------------------------------------------------------


def _count_prims(jaxpr, names) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            total += 1
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                total += _count_prims(sub, names)
            elif hasattr(val, "eqns"):
                total += _count_prims(val, names)
    return total


def test_fused_path_issues_fewer_launches_per_iteration():
    prob = _problem(seed=3)
    hoods, model = prob.hoods, prob.model
    labels0, mu0, sigma0 = em_mod.init_params(jax.random.PRNGKey(0), prob.graph.n_regions)
    carry = em_mod._MapCarry(
        labels=labels0,
        hist=jnp.zeros((em_mod.WINDOW + 1, hoods.n_hoods), jnp.float32),
        hood_energy=jnp.zeros((hoods.n_hoods,), jnp.float32),
        i=jnp.int32(0),
        done=jnp.bool_(False),
        diverged=jnp.bool_(False),
        msums=jnp.zeros((3, 2), jnp.float32),
    )

    def step(mode, backend, sctx):
        def f(labels, mu, sigma):
            c = carry._replace(labels=labels)
            return em_mod._map_step(
                hoods, model, mode, backend, sctx, em_mod.collectives.LOCAL,
                mu, sigma, c,
            )

        return jax.make_jaxpr(f)(labels0, mu0, sigma0).jaxpr

    # Keyed-reduction launches only: plain `scatter` eqns are .at[].set
    # slice/pad writes that XLA fuses away, so they don't count as launches.
    reduce_prims = {"scatter-add", "scatter-min", "scatter-max"}
    ctx = energy_mod.make_static_context(hoods, model, backend="pallas-interpret")
    # The unfused iteration as keyed scatters: 1 K-folded segment-sum
    # (per-(hood,label) counts) + 1 (hood sizes) + 1 (hood energy) + 1
    # K-folded vote scatter-add.  The static route reduces over the plan's
    # runs instead, and the fused route runs everything keyed inside
    # pallas_call.
    with mock.patch.object(energy_mod, "map_step_runs", scatter_map_step):
        n_scatter = _count_prims(step("static", "xla", ctx), reduce_prims)
    n_static = _count_prims(step("static", "xla", ctx), reduce_prims)
    fused_jaxpr = step("static-pallas", "pallas-interpret", ctx)
    n_fused = _count_prims(fused_jaxpr, reduce_prims)
    assert n_scatter >= 4
    assert n_fused < n_scatter
    assert n_fused == n_static == 0
    # ... and the fused path really is kernel launches, not hidden scatters.
    # The fused EM tick (DESIGN.md §16) folds the label-count pass into the
    # launch itself, so a whole MAP iteration is exactly ONE pallas_call
    # (it was two: segment-reduce counts + fused map-step).
    assert _count_prims(fused_jaxpr, {"pallas_call"}) == 1


# ---------------------------------------------------------------------------
# batched segment_volume
# ---------------------------------------------------------------------------


@pytest.mark.slow  # full-EM vmapped lockstep stack on CPU (~1 min)
def test_segment_volume_batched_matches_loop():
    vol = synthetic.make_synthetic_volume(seed=0, n_slices=3, shape=(48, 48))
    imgs = [np.asarray(im) for im in vol.images]
    res_b, _ = pipeline.segment_volume(imgs, overseg_grid=(6, 6), batch="always")
    res_l, _ = pipeline.segment_volume(imgs, overseg_grid=(6, 6), batch="never")
    assert len(res_b) == len(res_l) == 3
    for rb, rl in zip(res_b, res_l):
        np.testing.assert_array_equal(rb.region_labels, rl.region_labels)
        np.testing.assert_array_equal(rb.segmentation, rl.segmentation)
        assert rb.em_iters == rl.em_iters
        np.testing.assert_allclose(rb.mu, rl.mu, rtol=1e-5)


@pytest.mark.slow  # 8-slice full-EM batched trace on CPU (~2.5 min)
def test_segment_volume_8_slices_traces_run_em_once():
    # Fresh jit caches AND fresh api sessions: shape bucketing plus the
    # session-level executable cache are good enough that another test's
    # compiled run_em can otherwise be reused here (0 traces — which is the
    # feature, but makes the ==1 assertion order-dependent).  Slices have
    # data-dependent hood capacities, so the loop path would retrace.
    jax.clear_caches()
    api.reset_sessions()
    vol = synthetic.make_synthetic_volume(seed=5, n_slices=8, shape=(44, 44))
    imgs = [np.asarray(im) for im in vol.images]
    before = em_mod.TRACE_COUNTS["run_em"]
    res, _ = pipeline.segment_volume(imgs, overseg_grid=(6, 6), batch="always")
    traced = em_mod.TRACE_COUNTS["run_em"] - before
    assert traced == 1, f"batched 8-slice stack traced run_em {traced}x"
    assert len(res) == 8
    assert all(np.isfinite(r.total_energy) for r in res)


def test_segment_volume_rejects_bad_batch_arg():
    with pytest.raises(ValueError):
        pipeline.segment_volume([np.zeros((8, 8))], batch="maybe")


# ---------------------------------------------------------------------------
# compound_key overflow guard (satellite)
# ---------------------------------------------------------------------------


def test_compound_key_overflow_guard():
    major = jnp.asarray([1, 2], jnp.int32)
    minor = jnp.asarray([3, 4], jnp.int32)
    # fits: no error, values correct
    key = dpp.compound_key(major, minor, 10, major_span=3)
    np.testing.assert_array_equal(np.asarray(key), [13, 24])
    # does not fit the enabled integer width: loud failure, not silent wrap
    int_max = jnp.iinfo(jax.dtypes.canonicalize_dtype(jnp.int64)).max
    with pytest.raises(OverflowError):
        dpp.compound_key(major, minor, int_max, major_span=int_max)
