"""The paper's full 1813x1830 slice (arXiv 1809.05018 §4.1) through the
normal plan path, and the program against the benchmark's plain reference
(``bench/reference.py``) at an odd, non-square size.

At 1813x1830 with one region per 8x8 tile there are 51,983 regions and
about 10^5 cliques, so the (cliqueId, vertexId) key space of the
neighbourhood sort is past int32, and a dense (regions x regions)
adjacency would be 2.7 GB.  SLIC is skipped there (a fixed block grid is
handed in as the oversegmentation): its dense distance is a chip-sized
computation.
"""

import pathlib
import sys

import numpy as np
import pytest

from repro import api, obs
from repro.analysis import budget

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench import harness, reference, synthetic  # noqa: E402

CONFIG = "fullslice1813x1830-g227x229"


def test_plan_at_the_published_size_sorts_past_int32():
    h_px, w_px = harness.load_config(CONFIG)["shape"]
    gy, gx = -(-h_px // 8), -(-w_px // 8)
    yy, xx = np.mgrid[:h_px, :w_px]
    blocks = ((yy // 8) * gx + xx // 8).astype(np.int32)
    image = np.random.default_rng(0).uniform(0, 255, (h_px, w_px)).astype(np.float32)
    # A bucket grid of its own, so the hood class is new to this process.
    sess = api.Segmenter(api.ExecutionConfig(
        mode="static", capacity_bucket=65537, segment_bucket=4099))

    plan_counts = budget.LEDGER.section("plan")
    before = dict(plan_counts)
    with obs.recording() as rec:
        plan = sess.plan(image, oversegmentation=blocks)
        again = sess.plan(image, oversegmentation=blocks)
    grown = {k: plan_counts.get(k, 0) - before.get(k, 0)
             for k in ("hood_class_miss", "hood_class_hit")}
    assert grown == {"hood_class_miss": 1, "hood_class_hit": 1}
    assert [r.compiles for r in rec.named("plan.hoods")][1] == 0

    h, cs = plan.problem.hoods, plan.problem.cliques
    assert h.n_regions == gy * gx == 51_983
    assert (h.n_hoods + 1) * (h.n_regions + 1) > np.iinfo(np.int32).max

    nbrs, _, _ = reference.region_graph(image, blocks, h.n_regions)
    cliques = [tuple(int(v) for v in row[:k]) for row, k in zip(cs.members, cs.sizes)]
    assert cliques == reference.maximal_cliques(nbrs)
    want_vertex, want_hood = reference.neighbourhoods(cliques, nbrs)
    valid = np.asarray(h.valid)
    assert h.n_elements == want_vertex.shape[0] and valid[: h.n_elements].all()
    np.testing.assert_array_equal(np.asarray(h.vertex)[valid], want_vertex)
    np.testing.assert_array_equal(np.asarray(h.hood_id)[valid], want_hood)
    for field in ("vertex", "hood_id", "valid", "sizes", "offsets", "rep_old_index"):
        np.testing.assert_array_equal(np.asarray(getattr(again.problem.hoods, field)),
                                      np.asarray(getattr(h, field)), err_msg=field)


@pytest.mark.parametrize("seed", [5, 2**33 + 9])
def test_segment_matches_reference_at_an_odd_size(seed):
    cfg = harness.load_config(CONFIG)
    cfg.update(shape=[181, 183], overseg_grid=[23, 23], capacity_bucket=4096,
               segment_bucket=64)
    images, _ = synthetic.make_slices(seed, 1, cfg["shape"], cfg["corruption"])
    image = np.asarray(images[0])
    sess = harness.make_session(cfg)
    plan = sess.plan(image)
    got = sess.segment(image)
    ref = reference.segment(image, cfg)

    np.testing.assert_array_equal(plan.problem.labels_px, ref.superpixels)
    assert (plan.problem.hoods.n_hoods, plan.problem.hoods.n_elements) == (
        ref.n_hoods, ref.n_elements)
    np.testing.assert_array_equal(got.region_labels, ref.region_labels)
    np.testing.assert_array_equal(got.segmentation, ref.segmentation)
    # float32 sums in another order: the class means and the energy agree to
    # rounding, far inside the bench's limits (1e-5 and 1e-4).
    np.testing.assert_allclose(got.mu, ref.mu, rtol=1e-6)
    np.testing.assert_allclose(got.total_energy, ref.total_energy, rtol=1e-5)
    assert (got.em_iters, got.map_iters) == (ref.em_iters, ref.map_iters)
