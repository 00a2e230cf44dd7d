"""The single-device MAP iteration over the plan's sorted runs
(``energy.map_step_runs``, DESIGN.md §13).

* At the g64 bucket (``bench/configs/synthetic512-g64.json``) one traced
  ``static`` iteration holds no scatter-add and at most two gathers over
  the element capacity (``labels[vertex]`` and the argmin in vertex order).
* The ``em`` ledger counters say which reductions each traced iteration
  used: a single-device solve only the runs, the sharded route only the
  scatters.
* ``run_em`` gives the labels, parameters and iteration counts of the
  keyed-scatter iteration it replaced (``_scatter_map_step.py``), energies
  within 1e-5, at the slice's own shapes and padded to a larger bucket.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _scatter_map_step import run_em_scatter
from repro.analysis import budget
from repro.api import session as session_mod
from repro.core import synthetic
from repro.core.pmrf import collectives, pipeline
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as E
from repro.core.pmrf.distributed import partition_hoods, run_em_sharded
from repro.core.pmrf.hoods import pad_hoods

G64 = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
       / "synthetic512-g64.json")


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _abstract_step(bucket, n_labels, mode):
    hoods, model, labels, mu, sigma = session_mod._abstract_inputs(
        bucket, None, 1, n_labels
    )
    sctx = jax.eval_shape(E.make_static_context, hoods, model)
    f32 = jnp.float32
    carry = em_mod._MapCarry(
        labels=labels,
        hist=jax.ShapeDtypeStruct((em_mod.WINDOW + 1, bucket.n_hoods), f32),
        hood_energy=jax.ShapeDtypeStruct((bucket.n_hoods,), f32),
        i=jax.ShapeDtypeStruct((), jnp.int32),
        done=jax.ShapeDtypeStruct((), jnp.bool_),
        diverged=jax.ShapeDtypeStruct((), jnp.bool_),
        msums=jax.ShapeDtypeStruct((3, n_labels), f32),
    )

    def step(h, m, c, u, s, cy):
        return em_mod._map_step(h, m, mode, "xla", c, collectives.LOCAL, u, s, cy)

    return jax.make_jaxpr(step)(hoods, model, sctx, mu, sigma, carry).jaxpr


@pytest.mark.parametrize("n_labels", [2, 3])
def test_static_map_iteration_at_g64_has_no_element_wide_scatter(n_labels):
    cfg = json.loads(G64.read_text())
    bucket = session_mod.BucketKey(*cfg["bucket"]["value"])
    eqns = list(_eqns(_abstract_step(bucket, n_labels, "static")))
    names = [e.primitive.name for e in eqns]
    assert "scatter-add" not in names
    wide_scatters = [
        e for e in eqns if e.primitive.name.startswith("scatter")
        and e.invars[2].aval.shape[:1] == (bucket.capacity,)
    ]
    assert wide_scatters == []
    wide_gathers = [
        e for e in eqns if e.primitive.name == "gather"
        and e.invars[1].aval.shape[:1] == (bucket.capacity,)
    ]
    assert 1 <= len(wide_gathers) <= 2


def _problem(n_labels):
    shape, grid, seed = (48, 48), (6, 6), 0
    if n_labels == 2:
        vol = synthetic.make_synthetic_volume(seed=seed, n_slices=1, shape=shape)
    else:
        vol = synthetic.make_kary_volume(
            seed=seed, n_slices=1, shape=shape, n_phases=n_labels
        )
    prob = pipeline.initialize(
        np.asarray(vol.images[0]), overseg_grid=grid, n_labels=n_labels
    )
    init = em_mod.quantile_init(
        prob.graph.region_mean, prob.graph.n_regions, n_labels
    )
    return prob, init


def _em_counts():
    return dict(budget.LEDGER.section("em"))


def test_single_device_solves_count_only_run_reductions():
    prob, (l0, mu0, s0) = _problem(2)
    budget.LEDGER.reset("em")
    for mode in ("static", "faithful"):
        em_mod.run_em.trace(
            prob.hoods, prob.model, l0, mu0, s0, em_mod.EMConfig(mode=mode)
        )
    stack = jax.tree.map(lambda x: x[None], (prob.hoods, prob.model, l0, mu0, s0))
    # a config of its own, so the inner run_em is not a cached trace
    em_mod.run_em_batched.trace(*stack, em_mod.EMConfig(max_map_iters=9))
    got = _em_counts()
    assert got["run_reduce_traces"] == 3
    assert got["scatter_reduce_traces"] == 0


def test_sharded_route_counts_scatter_reductions():
    prob, (l0, mu0, s0) = _problem(2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    budget.LEDGER.reset("em")
    run_em_sharded.trace(
        partition_hoods(prob.hoods, 1), prob.model, l0, mu0, s0,
        config=em_mod.EMConfig(mode="static"), mesh=mesh,
    )
    got = _em_counts()
    assert got["scatter_reduce_traces"] == 1
    assert got["run_reduce_traces"] == 0


def _assert_same_solve(got, want):
    np.testing.assert_array_equal(np.asarray(got.labels), np.asarray(want.labels))
    np.testing.assert_array_equal(np.asarray(got.mu), np.asarray(want.mu))
    np.testing.assert_array_equal(np.asarray(got.sigma), np.asarray(want.sigma))
    assert int(got.em_iters) == int(want.em_iters)
    assert int(got.map_iters) == int(want.map_iters)
    assert int(got.status) == int(want.status)
    np.testing.assert_allclose(
        np.asarray(got.hood_energy), np.asarray(want.hood_energy), rtol=1e-5,
        atol=1e-5,
    )


@pytest.mark.parametrize("mode", ["static", "faithful"])
@pytest.mark.parametrize("n_labels", [2, 3, 5])
def test_run_em_matches_scatter_oracle(n_labels, mode):
    prob, (l0, mu0, s0) = _problem(n_labels)
    config = em_mod.EMConfig(mode=mode, max_em_iters=20, max_map_iters=10)
    want = run_em_scatter(prob.hoods, prob.model, l0, mu0, s0, config)
    got = em_mod.run_em(prob.hoods, prob.model, l0, mu0, s0, config)
    _assert_same_solve(got, want)


@pytest.mark.parametrize("n_labels", [2, 3])
def test_run_em_matches_scatter_oracle_padded_to_a_bucket(n_labels):
    # Phantom hoods, phantom regions and padding lanes past the slice's.
    prob, (l0, mu0, s0) = _problem(n_labels)
    h = prob.hoods
    nr = h.n_regions + 37
    hoods = pad_hoods(h, capacity=h.capacity + 3000, n_hoods=h.n_hoods + 61,
                      n_regions=nr)
    model = E.pad_model(prob.model, nr)
    labels0 = jnp.concatenate([l0, jnp.zeros((nr - h.n_regions,), jnp.int32)])
    config = em_mod.EMConfig(mode="static")
    want = run_em_scatter(hoods, model, labels0, mu0, s0, config)
    got = em_mod.run_em(hoods, model, labels0, mu0, s0, config)
    _assert_same_solve(got, want)
    unpadded = em_mod.run_em(h, prob.model, l0, mu0, s0, config)
    np.testing.assert_array_equal(
        np.asarray(got.labels)[: h.n_regions], np.asarray(unpadded.labels)[:-1]
    )
