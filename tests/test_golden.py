"""Cross-mode golden-oracle parity harness (DESIGN.md §13).

The checked-in fixtures under ``tests/golden/`` are the outputs of the
pure-NumPy float32 serial oracle (``reference.golden_em``) on three pinned
K-ary problems (K in {2, 3, 5}).  Every execution mode (faithful / static /
static-pallas) x kernel backend (xla / pallas-interpret) must reproduce the
oracle's **labels and iteration counts bit-exactly** and its energies to
fusion tolerance — pinning the whole EM/MAP stack (and every future
execution mode) to one serial reference instead of to each other.

Fixture format (deterministic bytes, so CI can diff regenerated output):

* ``k<K>_labels.npy`` — the oracle's final (V+1,) int32 label field
  (``np.save`` writes no timestamps, unlike ``np.savez``);
* ``k<K>_meta.json``  — mu/sigma (exact float32 values via repr), em/map
  iteration counts, total energy, and the problem spec that generated it.

Regeneration: ``pytest tests/test_golden.py --regenerate-golden`` rewrites
the fixtures from the oracle (the regen test runs first in file order, so
the parity tests below validate the fresh fixtures in the same session);
the ``tier1-multilabel`` CI job then fails on any nonempty
``git diff tests/golden/``.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import synthetic
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import pipeline, reference

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"

#: The pinned problems.  Small enough that the pallas-interpret matrix stays
#: cheap, large enough that every K label survives to convergence.
CASES = {
    2: dict(seed=0, shape=(48, 48), grid=(6, 6)),
    3: dict(seed=0, shape=(48, 48), grid=(6, 6)),
    5: dict(seed=1, shape=(48, 48), grid=(7, 7)),
}
MAX_EM, MAX_MAP = 20, 10

MODES = ("faithful", "static", "static-pallas")
BACKENDS = ("xla", "pallas-interpret")

_problem_cache = {}


def _build_problem(n_labels: int):
    """Deterministic K-ary problem + quantile init (no PRNG seeds to pin).

    The fixtures were generated from the non-partitionable threefry
    stream; JAX 0.5 made the partitionable one the default, which draws
    different volumes from the same seeds.  The image is therefore drawn
    under ``jax.threefry_partitionable(False)`` so the pinned problems stay
    the ones the fixtures describe.
    """
    if n_labels in _problem_cache:
        return _problem_cache[n_labels]
    spec = CASES[n_labels]
    with jax.threefry_partitionable(False):
        if n_labels == 2:
            vol = synthetic.make_synthetic_volume(
                seed=spec["seed"], n_slices=1, shape=spec["shape"]
            )
        else:
            vol = synthetic.make_kary_volume(
                seed=spec["seed"], n_slices=1, shape=spec["shape"],
                n_phases=n_labels,
            )
    prob = pipeline.initialize(
        np.asarray(vol.images[0]), overseg_grid=spec["grid"], n_labels=n_labels
    )
    labels0, mu0, sigma0 = em_mod.quantile_init(
        prob.graph.region_mean, prob.graph.n_regions, n_labels
    )
    out = (prob, np.asarray(labels0), np.asarray(mu0), np.asarray(sigma0))
    _problem_cache[n_labels] = out
    return out


def _run_oracle(n_labels: int) -> reference.RefResult:
    prob, labels0, mu0, sigma0 = _build_problem(n_labels)
    return reference.golden_em(
        prob.hoods, prob.model, labels0, mu0, sigma0,
        max_em_iters=MAX_EM, max_map_iters=MAX_MAP,
    )


def _fixture_paths(n_labels: int):
    return (
        GOLDEN_DIR / f"k{n_labels}_labels.npy",
        GOLDEN_DIR / f"k{n_labels}_meta.json",
    )


def _load_fixture(n_labels: int):
    labels_path, meta_path = _fixture_paths(n_labels)
    if not labels_path.exists() or not meta_path.exists():
        pytest.fail(
            f"missing golden fixture for K={n_labels}; run "
            "pytest tests/test_golden.py --regenerate-golden"
        )
    labels = np.load(labels_path)
    meta = json.loads(meta_path.read_text())
    return labels, meta


def _write_fixture(n_labels: int, res: reference.RefResult) -> None:
    GOLDEN_DIR.mkdir(exist_ok=True)
    labels_path, meta_path = _fixture_paths(n_labels)
    np.save(labels_path, np.asarray(res.labels, np.int32))
    spec = CASES[n_labels]
    meta = {
        "n_labels": n_labels,
        "seed": spec["seed"],
        "shape": list(spec["shape"]),
        "grid": list(spec["grid"]),
        "init": "quantile",
        "max_em_iters": MAX_EM,
        "max_map_iters": MAX_MAP,
        "em_iters": int(res.em_iters),
        "map_iters": int(res.map_iters),
        "mu": [float(v) for v in np.asarray(res.mu, np.float32)],
        "sigma": [float(v) for v in np.asarray(res.sigma, np.float32)],
        "total_energy": float(res.total_energy),
    }
    meta_path.write_text(json.dumps(meta, indent=2, sort_keys=True) + "\n")


# ---------------------------------------------------------------------------
# regeneration (runs FIRST in file order; active only with the flag)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_labels", sorted(CASES))
def test_regenerate_golden_fixtures(n_labels, regenerate_golden):
    if not regenerate_golden:
        pytest.skip("fixture regeneration only runs with --regenerate-golden")
    _write_fixture(n_labels, _run_oracle(n_labels))


# ---------------------------------------------------------------------------
# oracle self-consistency: the fixture really is the oracle's output
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_labels", sorted(CASES))
def test_fixture_matches_oracle(n_labels):
    labels, meta = _load_fixture(n_labels)
    res = _run_oracle(n_labels)
    np.testing.assert_array_equal(labels, res.labels)
    assert meta["em_iters"] == res.em_iters
    assert meta["map_iters"] == res.map_iters
    np.testing.assert_array_equal(
        np.asarray(meta["mu"], np.float32), res.mu
    )
    np.testing.assert_array_equal(
        np.asarray(meta["sigma"], np.float32), res.sigma
    )


# ---------------------------------------------------------------------------
# the harness: every mode x backend x K pins to the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_labels", sorted(CASES))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("mode", MODES)
def test_mode_matches_golden_oracle(mode, backend, n_labels):
    labels, meta = _load_fixture(n_labels)
    prob, labels0, mu0, sigma0 = _build_problem(n_labels)
    res = em_mod.run_em(
        prob.hoods, prob.model,
        jnp.asarray(labels0), jnp.asarray(mu0), jnp.asarray(sigma0),
        em_mod.EMConfig(
            mode=mode, backend=backend,
            max_em_iters=MAX_EM, max_map_iters=MAX_MAP,
        ),
    )
    tag = f"mode={mode} backend={backend} K={n_labels}"
    np.testing.assert_array_equal(np.asarray(res.labels), labels, err_msg=tag)
    assert int(res.em_iters) == meta["em_iters"], tag
    assert int(res.map_iters) == meta["map_iters"], tag
    want_mu = np.asarray(meta["mu"], np.float32)
    want_sigma = np.asarray(meta["sigma"], np.float32)
    if mode == "faithful":
        # faithful's M-step reduces in sorted order — same math, different
        # float accumulation order than the oracle's element order.
        np.testing.assert_allclose(np.asarray(res.mu), want_mu, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(res.sigma), want_sigma, rtol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(res.mu), want_mu, err_msg=tag)
        np.testing.assert_array_equal(
            np.asarray(res.sigma), want_sigma, err_msg=tag
        )
    # Energies carry the fusion-context caveat (one-hot dot vs scatter
    # accumulation order) — tolerance, not bits (DESIGN.md §12/§13).
    np.testing.assert_allclose(
        float(res.total_energy), meta["total_energy"], rtol=1e-4
    )


# ---------------------------------------------------------------------------
# precision tiers (DESIGN.md §16): the fused-tick precision knob gates a
# tolerance tier, never silently relaxes the bitwise one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_labels", sorted(CASES))
@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_tolerance_tier(backend, n_labels):
    """bf16 energy arithmetic: bounded drift against the f32 fixtures.

    The bf16 path quantizes only the per-element energies (f32
    accumulators, f32 M-step), so on the pinned problems the argmin
    decisions — and with them the labels, iteration counts, and the
    label-derived parameters — are expected to survive quantization;
    the accumulated total energy carries the visible drift.
    """
    labels, meta = _load_fixture(n_labels)
    prob, labels0, mu0, sigma0 = _build_problem(n_labels)
    res = em_mod.run_em(
        prob.hoods, prob.model,
        jnp.asarray(labels0), jnp.asarray(mu0), jnp.asarray(sigma0),
        em_mod.EMConfig(
            mode="static-pallas", backend=backend, precision="bf16",
            max_em_iters=MAX_EM, max_map_iters=MAX_MAP,
        ),
    )
    tag = f"bf16 backend={backend} K={n_labels}"
    agree = float(np.mean(np.asarray(res.labels) == labels))
    assert agree >= 0.95, f"{tag}: label agreement {agree:.4f}"
    np.testing.assert_allclose(
        np.asarray(res.mu), np.asarray(meta["mu"], np.float32),
        rtol=0.02, err_msg=tag,
    )
    np.testing.assert_allclose(
        np.asarray(res.sigma), np.asarray(meta["sigma"], np.float32),
        rtol=0.02, err_msg=tag,
    )
    np.testing.assert_allclose(
        float(res.total_energy), meta["total_energy"], rtol=0.02, err_msg=tag
    )


@pytest.mark.parametrize("n_labels", [2, 5])
def test_f32_knob_stays_bitwise(n_labels):
    """precision='f32' spelled explicitly is the bitwise tier — identical
    to the default-knob matrix above, pinned here so a future default flip
    can't silently downgrade the contract."""
    labels, meta = _load_fixture(n_labels)
    prob, labels0, mu0, sigma0 = _build_problem(n_labels)
    res = em_mod.run_em(
        prob.hoods, prob.model,
        jnp.asarray(labels0), jnp.asarray(mu0), jnp.asarray(sigma0),
        em_mod.EMConfig(
            mode="static-pallas", backend="pallas-interpret",
            precision="f32", max_em_iters=MAX_EM, max_map_iters=MAX_MAP,
        ),
    )
    np.testing.assert_array_equal(np.asarray(res.labels), labels)
    assert int(res.em_iters) == meta["em_iters"]
    np.testing.assert_array_equal(
        np.asarray(res.mu), np.asarray(meta["mu"], np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(res.sigma), np.asarray(meta["sigma"], np.float32)
    )


# ---------------------------------------------------------------------------
# the ticked serving pool reproduces the oracle too (static fast path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_labels", [2, 3])
def test_ticked_pool_matches_golden_oracle(n_labels):
    from repro import api

    labels, meta = _load_fixture(n_labels)
    prob, *_ = _build_problem(n_labels)
    spec = CASES[n_labels]
    sess = api.Segmenter(
        api.ExecutionConfig(
            overseg_grid=spec["grid"], n_labels=n_labels, init="quantile",
            max_em_iters=MAX_EM, max_map_iters=MAX_MAP,
        )
    )
    plan = api.session.Plan(
        problem=prob, bucket=sess.bucket_of(prob.hoods), init_seconds=0.0
    )
    bucket = plan.bucket
    exe = sess.compile_ticked(bucket, batch=2, tick_iters=4)
    hoods, model, state = sess.ticked_pool(bucket, batch=2)
    h1, m1, l0, mu0, sg0 = sess.lane_inputs(plan, bucket=bucket, seed=0)
    lane = em_mod.init_tick_lane(l0, mu0, sg0, bucket.n_hoods)
    write = jax.jit(
        lambda pools, lanes, slot: jax.tree.map(
            lambda p, o: p.at[slot].set(o), pools, lanes
        )
    )
    hoods, model, state = write((hoods, model, state), (h1, m1, lane), 0)
    for _ in range(200):
        state, _steps = exe(hoods, model, state)
        if bool(np.asarray(state.done)[0]):
            break
    else:
        pytest.fail("ticked lane did not converge")
    got = np.asarray(state.labels)[0]
    np.testing.assert_array_equal(got[: len(labels)], labels)
    assert int(np.asarray(state.em_i)[0]) == meta["em_iters"]
    np.testing.assert_array_equal(
        np.asarray(state.mu)[0], np.asarray(meta["mu"], np.float32)
    )
