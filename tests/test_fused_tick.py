"""Tests for the fused EM-tick kernel (DESIGN.md §16).

The single-launch tick performs the MAP iterate (per-hood label counts,
label-blocked energies, argmin, hood sums, votes), the M-step accumulators,
and the convergence predicate in one ``pallas_call``.  Pinned here:

* kernel vs XLA reference parity at both precisions, including multi-block
  problems that exercise the revisited-output accumulation;
* the launch ledger: one ``pallas_call`` per MAP iteration in ``run_em``
  and per micro-step in ``run_em_ticked`` on the fused route;
* the precision knob's validation and its cache-key split.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import synthetic
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import initialize
from repro.kernels import em_tick, ref
from repro.kernels import ops as kops


def _random_tick_problem(seed, n_labels, n_hoods, n_vertices, n):
    """Raw kernel operands, padding/validity included (like a real bucket)."""
    rng = np.random.default_rng(seed)
    hood_id = rng.integers(0, n_hoods, n).astype(np.int32)
    vertex = rng.integers(0, n_vertices - 1, n).astype(np.int32)
    valid = (rng.random(n) < 0.9).astype(np.float32)
    y = rng.normal(100, 30, n).astype(np.float32) * valid
    w = rng.random(n).astype(np.float32) * valid
    nall_e = rng.integers(1, 9, n).astype(np.float32)
    labels0 = rng.integers(0, n_labels, n_vertices).astype(np.int32)
    xf = labels0[vertex].astype(np.float32) * valid
    region_mean = rng.normal(100, 30, n_vertices).astype(np.float32)
    region_weight = rng.random(n_vertices).astype(np.float32)
    hist = np.full((em_mod.WINDOW + 1, n_hoods), 1e9, np.float32)
    hist[0] = rng.random(n_hoods).astype(np.float32) * 10
    mu = np.linspace(60, 140, n_labels).astype(np.float32)
    sigma = np.linspace(8, 14, n_labels).astype(np.float32)
    return [
        jnp.asarray(a)
        for a in (y, w, nall_e, xf, valid, hood_id, vertex,
                  region_mean, region_weight, hist, mu, sigma)
    ]


def _compare(r, p, *, hood_e_bitwise):
    """labels/votes are integer-exact -> bitwise; sums are dot-ordered."""
    np.testing.assert_array_equal(np.asarray(r[0]), np.asarray(p[0]))  # labels
    np.testing.assert_array_equal(np.asarray(r[2]), np.asarray(p[2]))  # votes
    assert bool(r[3]) == bool(p[3])                                    # conv
    if hood_e_bitwise:
        np.testing.assert_array_equal(np.asarray(r[1]), np.asarray(p[1]))
    else:
        np.testing.assert_allclose(
            np.asarray(r[1]), np.asarray(p[1]), rtol=1e-5, atol=1e-5
        )
    for i in (4, 5, 6):  # sum_w / sum_wy / sum_wyy
        np.testing.assert_allclose(
            np.asarray(r[i]), np.asarray(p[i]), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("n_labels", [2, 3, 5])
def test_fused_em_tick_pallas_matches_ref_single_block(n_labels):
    # Labels/votes are integer-exact -> bitwise.  The float sums ride the
    # tolerance tier even single-block: the kernel's one-hot dots reduce in
    # SIMD-blocked order, the reference's segment_sum in element order.
    args = _random_tick_problem(n_labels, n_labels, 37, 61, 900)
    kw = dict(n_hoods=37, n_vertices=61, precision="f32", conv_tol=1e-4)
    r = ref.fused_em_tick(*args, 0.75, **kw)
    p = em_tick.fused_em_tick_pallas(*args, 0.75, **kw, interpret=True)
    _compare(r, p, hood_e_bitwise=False)


@pytest.mark.parametrize("n_labels", [2, 5])
def test_fused_em_tick_pallas_matches_ref_multi_block(n_labels):
    # n > BLOCK: the kernel accumulates hood_e block-partial (ulp drift vs
    # the reference's flat segment order); integer-exact outputs stay
    # bitwise regardless of blocking.
    args = _random_tick_problem(n_labels, n_labels, 101, 257, 3000)
    kw = dict(n_hoods=101, n_vertices=257, precision="f32", conv_tol=1e-4)
    r = ref.fused_em_tick(*args, 0.75, **kw)
    p = em_tick.fused_em_tick_pallas(*args, 0.75, **kw, interpret=True)
    _compare(r, p, hood_e_bitwise=False)


@pytest.mark.parametrize("n_labels", [2, 3])
def test_fused_em_tick_bf16_kernel_matches_ref(n_labels):
    # Both routes share label_energies_blocked, so the bf16 energies (and
    # hence the argmins and labels) agree bitwise between kernel and ref.
    args = _random_tick_problem(n_labels + 10, n_labels, 64, 200, 2500)
    kw = dict(n_hoods=64, n_vertices=200, precision="bf16", conv_tol=1e-4)
    r = ref.fused_em_tick(*args, 0.75, **kw)
    p = em_tick.fused_em_tick_pallas(*args, 0.75, **kw, interpret=True)
    _compare(r, p, hood_e_bitwise=False)


def test_fused_em_tick_dispatch_and_vmem_fallback():
    args = _random_tick_problem(0, 2, 37, 61, 900)
    kw = dict(n_hoods=37, n_vertices=61)
    want = kops.fused_em_tick(*args, 0.75, backend="xla", **kw)
    got = kops.fused_em_tick(*args, 0.75, backend="pallas-interpret", **kw)
    _compare(want, got, hood_e_bitwise=False)
    # Over the one-hot VMEM ceiling the wrapper falls back to the xla
    # composition (with a FusedKernelDowngradeWarning), and the result
    # still matches the reference bitwise — it IS the reference.
    big_h, big_v = 1500, 700  # padded tiles: (1536+768)*BLOCK*4 B > 8 MB
    big_args = _random_tick_problem(1, 2, big_h, big_v, 2000)
    with pytest.warns(kops.FusedKernelDowngradeWarning, match="falling back"):
        got_big = kops.fused_em_tick(
            *big_args, 0.75, backend="pallas-interpret",
            n_hoods=big_h, n_vertices=big_v,
        )
    want_big = ref.fused_em_tick(
        *big_args, 0.75, n_hoods=big_h, n_vertices=big_v
    )
    _compare(want_big, got_big, hood_e_bitwise=True)


@pytest.mark.parametrize("op", ["fused_em_tick", "reduce_by_key"])
def test_size_guard_warns_when_auto_resolves_to_pallas(monkeypatch, op):
    # On a TPU "auto" resolves to pallas-tpu; a guard that then keeps the
    # work on XLA must say so exactly as for a named backend.
    from repro.core import dpp

    monkeypatch.delenv(kops.ENV_VAR, raising=False)
    monkeypatch.setattr(kops, "_auto_backend", lambda: "pallas-interpret")
    want = {
        "fused_em_tick": kops.FusedKernelDowngradeWarning,
        "reduce_by_key": kops.BackendDowngradeWarning,
    }[op]
    with pytest.warns(want, match=op):
        if op == "reduce_by_key":
            dpp.reduce_by_key(
                jnp.zeros((8,), jnp.int32), jnp.ones((8,), jnp.float32),
                kops.MAX_REDUCE_SEGMENTS + 1, backend="auto",
            )
        else:
            kops.fused_em_tick(
                *_random_tick_problem(1, 2, 1500, 700, 2000), 0.75,
                n_hoods=1500, n_vertices=700, backend="auto",
            )


# ---------------------------------------------------------------------------
# launch ledger: the fused route is ONE pallas_call per MAP iteration
# ---------------------------------------------------------------------------


def _prim_paths(jaxpr, names, path=""):
    """(path, eqn) for every matching primitive, path recording the
    enclosing higher-order primitives (while/scan/pjit/...)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            found.append((path, eqn))
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                found += _prim_paths(sub, names, path + f"/{eqn.primitive.name}")
            elif hasattr(val, "eqns"):
                found += _prim_paths(val, names, path + f"/{eqn.primitive.name}")
    return found


def _small_problem():
    vol = synthetic.make_synthetic_volume(seed=3, n_slices=1, shape=(48, 48))
    return initialize(np.asarray(vol.images[0]), overseg_grid=(6, 6))


def test_run_em_fused_route_is_one_launch_per_tick():
    prob = _small_problem()
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(0), prob.graph.n_regions
    )
    cfg = em_mod.EMConfig(mode="static-pallas", backend="pallas-interpret")
    traced = em_mod.run_em.trace(
        prob.hoods, prob.model, labels0, mu0, sigma0, cfg
    )
    calls = _prim_paths(traced.jaxpr.jaxpr, {"pallas_call"})
    # Exactly one pallas_call inside the EM/MAP while-loop nest: counts,
    # energies, reductions, M-sums, and convergence all ride one launch
    # per MAP iteration.  Anything outside the loops (the final-energy
    # epilogue) runs once per run_em call, not per tick.
    in_loop = [p for p, _ in calls if "while" in p]
    assert len(in_loop) == 1, [p for p, _ in calls]


def test_run_em_ticked_fused_route_is_one_launch_per_tick():
    prob = _small_problem()
    sess = api.Segmenter(
        api.ExecutionConfig(
            mode="static-pallas", backend="pallas-interpret",
            overseg_grid=(6, 6),
        )
    )
    bucket = sess.bucket_of(prob.hoods)
    hoods, model, state = sess.ticked_pool(bucket, batch=2)
    emc = sess.config.em_config()
    traced = em_mod.run_em_ticked.trace(hoods, model, state, emc, 2)
    # The tick's loop body holds exactly one launch, the fused tick, and
    # nothing else in the ticked program launches a kernel at all: the
    # hoisted hood sizes are run sums, not a segment-reduce launch.
    calls = _prim_paths(traced.jaxpr.jaxpr, {"pallas_call"})
    assert len(calls) == 1, [p for p, _ in calls]
    assert "while" in calls[0][0]


# ---------------------------------------------------------------------------
# precision knob: validation + cache-key split
# ---------------------------------------------------------------------------


def test_precision_validation():
    prob = _small_problem()
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(0), prob.graph.n_regions
    )
    with pytest.raises(ValueError, match="precision"):
        em_mod.run_em(
            prob.hoods, prob.model, labels0, mu0, sigma0,
            em_mod.EMConfig(mode="static", precision="bf16"),
        )
    with pytest.raises(ValueError, match="precision"):
        em_mod.run_em(
            prob.hoods, prob.model, labels0, mu0, sigma0,
            em_mod.EMConfig(mode="static-pallas", precision="f16"),
        )
    with pytest.raises(ValueError, match="bf16"):
        api.ExecutionConfig(mode="static", precision="bf16")
    with pytest.raises(ValueError, match="precision"):
        api.ExecutionConfig(precision="f64")


def test_precision_splits_executable_cache_key():
    f32 = api.Segmenter(api.ExecutionConfig(mode="static-pallas"))
    bf16 = api.Segmenter(
        api.ExecutionConfig(mode="static-pallas", precision="bf16")
    )
    bucket = api.session.BucketKey(256, 64, 64)
    k32 = f32._key_for(bucket, batch=None)
    k16 = bf16._key_for(bucket, batch=None)
    assert k32.precision == "f32" and k16.precision == "bf16"
    assert k32 != k16
    assert k32 == k32._replace(precision="bf16")._replace(precision="f32")


def test_bf16_route_bounded_drift_vs_f32():
    # End-to-end: the bf16 fused tick must land near the f32 route on a
    # real problem — labels mostly agree, parameters within percent-level
    # drift (the bounded-drift tier; exact bounds live in test_golden).
    prob = _small_problem()
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(0), prob.graph.n_regions
    )
    res = {}
    for precision in ("f32", "bf16"):
        res[precision] = em_mod.run_em(
            prob.hoods, prob.model, labels0, mu0, sigma0,
            em_mod.EMConfig(
                mode="static-pallas", backend="pallas-interpret",
                precision=precision,
            ),
        )
    a, b = res["f32"], res["bf16"]
    agree = np.mean(np.asarray(a.labels) == np.asarray(b.labels))
    assert agree >= 0.9, f"bf16 label agreement {agree:.3f}"
    np.testing.assert_allclose(np.asarray(a.mu), np.asarray(b.mu), rtol=0.05)
    np.testing.assert_allclose(
        np.asarray(a.sigma), np.asarray(b.sigma), rtol=0.1
    )
