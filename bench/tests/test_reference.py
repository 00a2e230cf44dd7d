"""The benchmark's reference against the program, at a tiny size on the CPU:
both compute the same method, so they agree exactly on every discrete
product and to float32 rounding on the class parameters."""

from __future__ import annotations

import numpy as np
import pytest

from bench import harness, reference, synthetic
from bench.tests.conftest import tiny_config


@pytest.mark.parametrize("config", ["synthetic512-g64", "synthetic512-g16-fused"])
@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_reference_matches_program(tiny, config, seed):
    cfg = tiny(config)
    images, _ = synthetic.make_slices(seed, 1, cfg["shape"], cfg["corruption"])
    image = np.asarray(images[0])
    sess = harness.make_session(cfg)
    plan = sess.plan(image)
    got = sess.execute(plan)
    ref = reference.segment(image, cfg)

    np.testing.assert_array_equal(plan.problem.labels_px, ref.superpixels)
    assert (plan.problem.hoods.n_hoods, plan.problem.hoods.n_elements) == (
        ref.n_hoods, ref.n_elements)
    np.testing.assert_array_equal(got.region_labels, ref.region_labels)
    np.testing.assert_array_equal(got.segmentation, ref.segmentation)
    np.testing.assert_allclose(got.mu, ref.mu, rtol=1e-6)
    # sigma comes from E[y^2] - mu^2 in float32, which cancels about three
    # digits here (mu/sigma is about 10 to 30): the fused kernel and the
    # reference round that difference apart.
    np.testing.assert_allclose(got.sigma, ref.sigma, rtol=1e-4)
    assert (got.em_iters, got.map_iters) == (ref.em_iters, ref.map_iters)


def test_cliques_match_program():
    cfg = tiny_config("synthetic512-g64")
    images, _ = synthetic.make_slices(11, 1, cfg["shape"], cfg["corruption"])
    plan = harness.make_session(cfg).plan(np.asarray(images[0]))
    sp = plan.problem.labels_px
    nbrs, _, _ = reference.region_graph(np.asarray(images[0]), sp, 64)
    got = [tuple(int(v) for v in row[:n]) for row, n in
           zip(plan.problem.cliques.members, plan.problem.cliques.sizes)]
    assert got == reference.maximal_cliques(nbrs)


def test_bf16_reference_departs():
    """The control computes in bfloat16 and lands elsewhere."""
    cfg = tiny_config("synthetic512-g64")
    images, _ = synthetic.make_slices(5, 1, cfg["shape"], cfg["corruption"])
    f32 = reference.segment(np.asarray(images[0]), cfg)
    bf16 = reference.segment(np.asarray(images[0]), cfg, "bf16")
    assert np.max(np.abs(bf16.mu - f32.mu) / np.abs(f32.mu)) > 1e-4


@pytest.mark.parametrize("cell", ["synthetic512-g64.solve", "synthetic512-g64.volume"])
def test_control_is_not_correct(cell, tiny, cache_dir):
    """The control of a ``static`` cell, the bfloat16 reference in the
    program's place, comes out not correct under the cell's own limits; a
    sound run of the program under the same limits comes out correct."""
    from bench import control

    cfg = tiny("synthetic512-g64")
    bad = control.readings(cell, 8, 0.5, True, require_tpu=False, config=cfg,
                           cache_dir=cache_dir)
    assert bad["correct"] is False, bad
    good = control.readings(cell, 8, 0.5, False, require_tpu=False, config=cfg,
                            cache_dir=cache_dir)
    assert good["correct"] is True, good
