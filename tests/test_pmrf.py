"""Integration + correctness tests for the PMRF engine.

Covers: graph construction vs. a brute-force oracle, clique maximality,
neighborhood structure invariants, faithful-vs-static mode equivalence,
energy monotonicity, and the paper's verification claim (high accuracy vs.
ground truth on the synthetic porous-media benchmark, §4.2.2).
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics, oversegment, synthetic
from repro.core.pmrf import (
    EMConfig,
    build_hoods,
    build_region_graph,
    enumerate_maximal_cliques,
    initialize,
    optimize,
    run_em,
    segment_image,
)
from repro.analysis import budget
from repro.core.pmrf.cliques import CliqueSet, verify_maximal_cliques
from repro.core.pmrf.graph import RegionGraph
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as energy_mod
from repro.core.pmrf import hoods as hoods_mod
from repro import obs

from _dense_cliques import dense_adjacency, dense_maximal_cliques
from _hyp import given, settings, st
from _natural_hoods import natural_hoods

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench import reference as bench_reference  # noqa: E402


def _tiny_problem(seed=0, shape=(40, 40), grid=(6, 6)):
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=1, shape=shape)
    img = np.asarray(vol.images[0])
    gt = np.asarray(vol.ground_truth[0])
    return img, gt


# ---------------------------------------------------------------------------
# Graph
# ---------------------------------------------------------------------------


def test_region_graph_matches_bruteforce():
    lab = np.array(
        [
            [0, 0, 1, 1],
            [0, 2, 2, 1],
            [3, 2, 2, 4],
            [3, 3, 4, 4],
        ],
        dtype=np.int32,
    )
    img = np.arange(16, dtype=np.float32).reshape(4, 4)
    g = build_region_graph(img, lab, 5)

    want_edges = set()
    for y in range(4):
        for x in range(4):
            for dy, dx in ((0, 1), (1, 0)):
                yy, xx = y + dy, x + dx
                if yy < 4 and xx < 4 and lab[y, x] != lab[yy, xx]:
                    want_edges.add(tuple(sorted((lab[y, x], lab[yy, xx]))))
    got_edges = {tuple(e) for e in g.edges.tolist()}
    assert got_edges == want_edges

    for r in range(5):
        mask = lab == r
        np.testing.assert_allclose(g.region_mean[r], img[mask].mean(), rtol=1e-5)
        assert g.region_size[r] == mask.sum()

    # CSR rows are the brute-force neighbor sets, ascending within each row
    for v in range(5):
        row = g.csr_neighbors[g.csr_offsets[v] : g.csr_offsets[v + 1]].tolist()
        assert row == sorted({b if a == v else a for a, b in want_edges if v in (a, b)})
    assert g.edge_keys.tolist() == sorted(a * 5 + b for a, b in want_edges)


def test_cliques_are_maximal_on_random_planarish_graph():
    img, _ = _tiny_problem()
    lab = oversegment.slic(jnp.asarray(img), grid=(6, 6), iters=3)
    g = build_region_graph(img, lab, 36)
    cs = enumerate_maximal_cliques(g)
    assert cs.n_cliques > 0
    assert verify_maximal_cliques(g, cs)
    # every edge must be covered by some maximal clique
    covered = set()
    for row, size in zip(cs.members, cs.sizes):
        mem = row[:size].tolist()
        for i in range(size):
            for j in range(i + 1, size):
                covered.add(tuple(sorted((mem[i], mem[j]))))
    assert {tuple(e) for e in g.edges.tolist()} <= covered


def _edge_graph(n, pairs):
    """A RegionGraph of ``n`` vertices and the undirected edges ``pairs``."""
    keys = np.unique(np.array(
        [min(a, b) * n + max(a, b) for a, b in pairs if a != b], np.int64))
    return RegionGraph.from_edge_keys(
        keys, n, np.zeros(n, np.float32), np.ones(n, np.float32))


def _neighbour_sets(g):
    return [set(g.csr_neighbors[g.csr_offsets[v]:g.csr_offsets[v + 1]].tolist())
            for v in range(g.n_regions)]


def _assert_cliques_match_oracles(g):
    """The CSR enumerator equals the dense one (members, order, width) and
    the benchmark's Bron-Kerbosch reference."""
    got, want = enumerate_maximal_cliques(g), dense_maximal_cliques(g)
    assert got.members.dtype == want.members.dtype
    np.testing.assert_array_equal(got.members, want.members)
    np.testing.assert_array_equal(got.sizes, want.sizes)
    rows = [tuple(r[:k].tolist()) for r, k in zip(got.members, got.sizes)]
    assert rows == bench_reference.maximal_cliques(_neighbour_sets(g))
    assert verify_maximal_cliques(g, got)
    return got


@settings(max_examples=80, deadline=None)
@given(st.integers(1, 14),
       st.lists(st.tuples(st.integers(0, 13), st.integers(0, 13)), max_size=70))
def test_csr_cliques_match_dense_on_random_graphs(n, pairs):
    _assert_cliques_match_oracles(_edge_graph(n, [(a % n, b % n) for a, b in pairs]))


@pytest.mark.parametrize("shape,grid,seed", [
    ((40, 40), (6, 6), 0), ((96, 80), (12, 10), 1), ((128, 128), (16, 16), 2),
    ((181, 183), (23, 23), 3),
])
def test_csr_cliques_match_dense_on_slic_graphs(shape, grid, seed):
    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=1, shape=shape)
    img = np.asarray(vol.images[0])
    lab = oversegment.slic(jnp.asarray(img), grid=grid, iters=3)
    g = build_region_graph(img, lab, grid[0] * grid[1])
    before = budget.LEDGER.section("plan").get("clique_candidates", 0)
    cs = _assert_cliques_match_oracles(g)
    assert cs.width >= 3
    # Level 2 alone tests every neighbor of every edge's larger end.
    grown = budget.LEDGER.section("plan")["clique_candidates"] - before
    assert grown >= int(g.degree()[g.edges[:, 1]].sum())


def test_verify_maximal_cliques_refuses_non_maximal_and_non_cliques():
    g = _edge_graph(4, [(0, 1), (1, 2), (0, 2), (2, 3)])
    good = enumerate_maximal_cliques(g)
    assert good.members.tolist() == [[2, 3, -1], [0, 1, 2]]
    assert verify_maximal_cliques(g, good)
    extendable = CliqueSet(np.array([[0, 1]], np.int32), np.array([2], np.int32))
    assert not verify_maximal_cliques(g, extendable)
    not_a_clique = CliqueSet(np.array([[0, 3]], np.int32), np.array([2], np.int32))
    assert not verify_maximal_cliques(g, not_a_clique)


def test_hoods_structure():
    img, _ = _tiny_problem()
    lab = oversegment.slic(jnp.asarray(img), grid=(6, 6), iters=3)
    g = build_region_graph(img, lab, 36)
    cs = enumerate_maximal_cliques(g)
    hoods = build_hoods(g, cs)
    adj = dense_adjacency(g)

    vertex = np.asarray(hoods.vertex)
    hood_id = np.asarray(hoods.hood_id)
    valid = np.asarray(hoods.valid)
    sizes = np.asarray(hoods.sizes)

    assert hoods.n_hoods == cs.n_cliques
    assert sizes.sum() == valid.sum() == hoods.n_elements

    # Oracle: hood h = clique members U their 1-hop neighbors.
    got = {}
    for hid, v in zip(hood_id[valid], vertex[valid]):
        got.setdefault(int(hid), set()).add(int(v))
    for h in range(cs.n_cliques):
        mem = cs.members[h][: cs.sizes[h]].tolist()
        want = set(mem)
        for m in mem:
            want |= set(np.nonzero(adj[m])[0].tolist())
        assert got.get(h, set()) == want, f"hood {h} mismatch"
        assert sizes[h] == len(want)

    # no duplicates within a hood (the SortByKey+Unique step)
    pairs = list(zip(hood_id[valid].tolist(), vertex[valid].tolist()))
    assert len(pairs) == len(set(pairs))

    # replication arrays: each valid element appears exactly twice
    rep_old = np.asarray(hoods.rep_old_index)[np.asarray(hoods.rep_valid)]
    counts = np.bincount(rep_old, minlength=hoods.capacity)
    np.testing.assert_array_equal(counts[valid], 2)
    assert counts[~valid].sum() == 0
    # ... once per test label
    rep_lab = np.asarray(hoods.rep_test_label)[np.asarray(hoods.rep_valid)]
    assert rep_lab.sum() == valid.sum()


@pytest.mark.parametrize("n_regions", [5, 70000])
def test_vote_order_is_a_stable_vertex_sort(n_regions):
    # 70000 regions need the radix sort's second 16-bit digit.
    rng = np.random.default_rng(n_regions)
    vertex = rng.integers(0, n_regions + 1, 5000).astype(np.int32)
    perm, bounds = hoods_mod.vote_order(vertex, n_regions)
    np.testing.assert_array_equal(perm, np.argsort(vertex, kind="stable"))
    np.testing.assert_array_equal(
        bounds, np.searchsorted(vertex[perm], np.arange(n_regions + 2)))
    assert perm.dtype == bounds.dtype == np.int32


def test_vote_order_survives_padding_to_a_bucket():
    img, _ = _tiny_problem()
    lab = oversegment.slic(jnp.asarray(img), grid=(6, 6), iters=3)
    g = build_region_graph(img, lab, 36)
    hoods = build_hoods(g, enumerate_maximal_cliques(g))
    padded = hoods_mod.pad_hoods(
        hoods, capacity=hoods.capacity + 700, n_hoods=hoods.n_hoods + 9,
        n_regions=hoods.n_regions + 5)
    for h in (hoods, padded):
        perm, bounds = hoods_mod.vote_order(np.asarray(h.vertex), h.n_regions)
        np.testing.assert_array_equal(np.asarray(h.vote_perm), perm)
        np.testing.assert_array_equal(np.asarray(h.vote_bounds), bounds)


def _voronoi_graph(seed, n_regions=60, shape=(40, 40)):
    """Region graph of a random Voronoi partition: a random planar graph."""
    rng = np.random.default_rng(seed)
    cells = rng.choice(shape[0] * shape[1], n_regions, replace=False)
    cy, cx = np.divmod(cells, shape[1])
    yy, xx = np.mgrid[: shape[0], : shape[1]]
    d = (yy[..., None] - cy) ** 2 + (xx[..., None] - cx) ** 2
    lab = np.argmin(d, axis=-1).astype(np.int32)
    img = rng.uniform(0, 255, shape).astype(np.float32)
    return build_region_graph(img, lab, n_regions)


def _widened(cs, extra):
    """The same cliques with ``extra`` more -1 columns (a wider W)."""
    return CliqueSet(np.pad(cs.members, ((0, 0), (0, extra)), constant_values=-1),
                     cs.sizes)


@pytest.fixture(scope="module")
def slic512_graph():
    vol = synthetic.make_synthetic_volume(seed=2, n_slices=1, shape=(512, 512))
    img = np.asarray(vol.images[0])
    lab = oversegment.slic(jnp.asarray(img), grid=(8, 8), iters=3)
    return build_region_graph(img, lab, 64)


def _natural_lanes(g, cs):
    deg = np.diff(g.csr_offsets)
    return int(deg[cs.members[cs.members >= 0]].sum()) + cs.members.size


# (graph, capacity_bucket, segment_bucket); a bucket given as a string is
# derived from the graph: "lanes" puts the natural lane count exactly on a
# capacity boundary, "cliques" makes C a multiple of segment_bucket.
_HOOD_CASES = {
    "voronoi-0": ("voronoi-0", 256, 64),
    "voronoi-1": ("voronoi-1", 1000, 7),
    "voronoi-2": ("voronoi-2", 1, 1),
    "voronoi-3-wide": ("voronoi-3-wide", 4096, 1024),
    "capacity-on-boundary": ("voronoi-4", "lanes", 1),
    "cliques-multiple-of-bucket": ("voronoi-5", 256, "cliques"),
    "slic512": ("slic512", 4096, 64),
}


@pytest.mark.parametrize("case", sorted(_HOOD_CASES))
def test_build_hoods_matches_natural_oracle(case, request):
    graph_name, cap_b, seg_b = _HOOD_CASES[case]
    if graph_name == "slic512":
        g = request.getfixturevalue("slic512_graph")
    else:
        g = _voronoi_graph(int(graph_name.split("-")[1]))
    cs = enumerate_maximal_cliques(g)
    if graph_name.endswith("-wide"):
        cs = _widened(cs, 2)
    if cap_b == "lanes":
        cap_b = _natural_lanes(g, cs)
    if seg_b == "cliques":
        seg_b = cs.n_cliques // 2 if cs.n_cliques % 2 == 0 else cs.n_cliques
        assert cs.n_cliques % seg_b == 0
    got = build_hoods(g, cs, capacity_bucket=cap_b, segment_bucket=seg_b)
    want = natural_hoods(g, cs)

    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def  # n_hoods, n_regions, n_elements included
    assert (got.n_hoods, got.n_regions, got.n_elements) == (
        want.n_hoods, want.n_regions, want.n_elements)
    for field in ("vertex", "hood_id", "valid", "sizes", "offsets", "rep_old_index",
                  "rep_test_label", "rep_hood_id", "rep_valid", "vote_perm",
                  "vote_bounds"):
        a, b = getattr(got, field), getattr(want, field)
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=field)
    assert len(got_leaves) == len(want_leaves)


def test_hood_program_compiles_once_per_class():
    # Buckets no other test uses, so the class is new to this process.
    grid = dict(capacity_bucket=8191, segment_bucket=509)
    g1, g2 = _voronoi_graph(11), _voronoi_graph(12)
    cs1, cs2 = enumerate_maximal_cliques(g1), enumerate_maximal_cliques(g2)
    assert cs1.n_cliques != cs2.n_cliques
    assert g1.csr_neighbors.shape != g2.csr_neighbors.shape
    assert _natural_lanes(g1, cs1) != _natural_lanes(g2, cs2)

    plan = budget.LEDGER.section("plan")
    with obs.recording() as rec:
        with obs.span("plan.hoods"):
            h1 = build_hoods(g1, cs1, **grid)
        assert (plan.get("hood_class_miss", 0), plan.get("hood_class_hit", 0)) == (1, 0)
        with obs.span("plan.hoods"):
            h2 = build_hoods(g2, cs2, **grid)
        assert (plan.get("hood_class_miss", 0), plan.get("hood_class_hit", 0)) == (1, 1)
        with obs.span("plan.hoods"):
            # The clique width is no class dimension: it varies per slice.
            h3 = build_hoods(g1, _widened(cs1, 2), **grid)
    first, second, wider = rec.records
    assert (first.compiles, second.compiles, wider.compiles) == (1, 0, 0)
    assert (plan["hood_class_miss"], plan["hood_class_hit"]) == (1, 2)
    assert h1.capacity != h2.capacity  # natural shapes out, one program in
    assert h3.capacity == h1.capacity + 2 * cs1.n_cliques


# ---------------------------------------------------------------------------
# EM optimization
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_faithful_and_static_modes_agree():
    img, _ = _tiny_problem(seed=3)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, mu0, sigma0 = em_mod.init_params(jax.random.PRNGKey(7), problem.graph.n_regions)

    res_s = run_em(problem.hoods, problem.model, labels0, mu0, sigma0,
                   EMConfig(mode="static"))
    res_f = run_em(problem.hoods, problem.model, labels0, mu0, sigma0,
                   EMConfig(mode="faithful"))

    np.testing.assert_array_equal(np.asarray(res_s.labels), np.asarray(res_f.labels))
    np.testing.assert_allclose(np.asarray(res_s.mu), np.asarray(res_f.mu), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(res_s.total_energy), np.asarray(res_f.total_energy), rtol=1e-5
    )
    assert int(res_s.em_iters) == int(res_f.em_iters)


def test_min_energy_modes_agree_elementwise():
    img, _ = _tiny_problem(seed=5)
    problem = initialize(img, overseg_grid=(6, 6))
    hoods, model = problem.hoods, problem.model
    labels0, mu0, sigma0 = em_mod.init_params(jax.random.PRNGKey(1), problem.graph.n_regions)
    energies = energy_mod.label_energies(hoods, model, labels0, mu0, sigma0)
    e_s, a_s = energy_mod.min_energies_static(energies)
    e_f, a_f = energy_mod.min_energies_faithful(hoods, energies)
    valid = np.asarray(hoods.valid)
    np.testing.assert_allclose(np.asarray(e_s)[valid], np.asarray(e_f)[valid], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a_s)[valid], np.asarray(a_f)[valid])


@pytest.mark.slow
def test_energy_decreases_across_em():
    """MAP label updates must not increase the total energy (given fixed
    params the vote/min step minimizes elementwise energy)."""
    img, _ = _tiny_problem(seed=11)
    problem = initialize(img, overseg_grid=(6, 6))
    res = optimize(problem, seed=0, config=EMConfig(max_em_iters=8))
    # run again with more iterations: energy should be no worse
    res2 = optimize(problem, seed=0, config=EMConfig(max_em_iters=20))
    assert float(res2.total_energy) <= float(res.total_energy) * 1.05


@pytest.mark.slow
def test_segmentation_accuracy_synthetic():
    """Paper §4.2.2: high precision/recall/accuracy vs. ground truth on the
    synthetic porous-media data (paper: 99.3/98.3/98.6 on full-res; we use a
    reduced volume and require a comfortable bar).  (64, 64) @ grid 16 is
    the smallest shape that keeps the bars comfortably clear — the CI
    timing-budget trim, DESIGN.md §13.)"""
    vol = synthetic.make_synthetic_volume(seed=0, n_slices=1, shape=(64, 64))
    img = np.asarray(vol.images[0])
    gt = np.asarray(vol.ground_truth[0])
    res = segment_image(img, overseg_grid=(16, 16), seed=0)
    m = metrics.evaluate(res.segmentation, gt)
    assert m.accuracy > 0.90, m
    assert m.precision > 0.85, m
    assert m.recall > 0.85, m


@pytest.mark.slow
def test_mrf_beats_threshold_baseline():
    vol = synthetic.make_synthetic_volume(
        seed=2, n_slices=1, shape=(64, 64), gaussian_sigma=70.0
    )
    img = np.asarray(vol.images[0])
    gt = np.asarray(vol.ground_truth[0])
    res = segment_image(img, overseg_grid=(16, 16), seed=0)
    m_mrf = metrics.evaluate(res.segmentation, gt)
    m_thr = metrics.evaluate(np.asarray(synthetic.threshold_baseline(jnp.asarray(img))), gt)
    assert m_mrf.accuracy > m_thr.accuracy, (m_mrf, m_thr)


@pytest.mark.slow
def test_em_converges_within_paper_budget():
    img, _ = _tiny_problem(seed=4)
    res = segment_image(img, overseg_grid=(6, 6), seed=0)
    assert res.em_iters <= 20  # the paper's observed convergence budget
    assert np.isfinite(res.total_energy)


# ---------------------------------------------------------------------------
# Health status lattice (DESIGN.md §14): diverged / degenerate detection
# ---------------------------------------------------------------------------


def test_healthy_run_reports_converged_status():
    img, _ = _tiny_problem(seed=3)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(7), problem.graph.n_regions
    )
    res = run_em(problem.hoods, problem.model, labels0, mu0, sigma0, EMConfig())
    assert int(res.status) == em_mod.STATUS_CONVERGED
    assert em_mod.STATUS_NAMES[int(res.status)] == "converged"


@pytest.mark.parametrize("mode", ["faithful", "static"])
def test_nan_init_is_flagged_diverged_not_propagated(mode):
    """Non-finite initial mu -> every energy is NaN; the run must terminate
    at its first boundary with STATUS_DIVERGED instead of looping to the
    iteration cap on NaN comparisons."""
    img, _ = _tiny_problem(seed=3)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, mu0, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(7), problem.graph.n_regions
    )
    res = run_em(
        problem.hoods, problem.model, labels0,
        jnp.full_like(mu0, jnp.nan), sigma0, EMConfig(mode=mode),
    )
    assert int(res.status) == em_mod.STATUS_DIVERGED
    assert int(res.em_iters) <= 1  # caught at the first EM boundary
    # labels stay finite ints even though params are garbage
    assert np.asarray(res.labels).dtype.kind == "i"


def test_duplicate_mu_init_recovers_or_flags_never_nans():
    """Both components seeded at the same mu (zero separation): the run
    must end with finite parameters — either the reseed machinery recovers
    a live two-component fit (CONVERGED/MAX_ITERS) or the collapse is
    reported as DEGENERATE.  Silent NaN is the one forbidden outcome."""
    img, _ = _tiny_problem(seed=3)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, _, sigma0 = em_mod.init_params(
        jax.random.PRNGKey(7), problem.graph.n_regions
    )
    mu_dup = jnp.full_like(sigma0, float(np.asarray(img).mean()))
    res = run_em(problem.hoods, problem.model, labels0, mu_dup, sigma0, EMConfig())
    assert int(res.status) != em_mod.STATUS_DIVERGED
    assert np.isfinite(np.asarray(res.mu)).all()
    assert np.isfinite(np.asarray(res.sigma)).all()
    assert np.isfinite(float(res.total_energy))


def test_constant_image_collapse_is_flagged_degenerate():
    """A zero-variance image with quantile init: both quantiles coincide,
    one component ends massless with sigma pinned at sigma_min -> the
    boundary check must report DEGENERATE with finite parameters (the
    documented alternative is a successful reseed recovery; a constant
    image leaves the reseed nothing to separate)."""
    img = np.full((40, 40), 7.0, np.float32)
    img += np.random.default_rng(0).normal(0, 1e-3, img.shape).astype(np.float32)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, mu0, sigma0 = em_mod.quantile_init(
        problem.graph.region_mean, problem.graph.n_regions
    )
    res = run_em(problem.hoods, problem.model, labels0, mu0, sigma0, EMConfig())
    assert int(res.status) == em_mod.STATUS_DEGENERATE
    assert np.isfinite(np.asarray(res.mu)).all()
    assert np.isfinite(np.asarray(res.sigma)).all()


def test_two_phase_image_with_quantile_init_not_flagged():
    """Degeneracy must not false-positive: a clean two-phase image with
    well-separated quantile init converges with both components live."""
    img, _ = _tiny_problem(seed=3)
    problem = initialize(img, overseg_grid=(6, 6))
    labels0, mu0, sigma0 = em_mod.quantile_init(
        problem.graph.region_mean, problem.graph.n_regions
    )
    res = run_em(problem.hoods, problem.model, labels0, mu0, sigma0, EMConfig())
    assert int(res.status) in (em_mod.STATUS_CONVERGED, em_mod.STATUS_MAX_ITERS)
