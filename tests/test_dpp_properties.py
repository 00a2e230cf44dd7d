"""Property-based tests for the DPP primitive layer (``core/dpp.py``).

Hypothesis drives random shapes/values through the primitives and checks
them against numpy oracles and against each other across kernel backends
(``xla`` vs ``pallas-interpret`` — the same lockstep the CI matrix
enforces suite-wide, here concentrated on the keyed-reduction entry point
with randomized inputs).  Each property also has a pinned example-based
companion so the file still exercises the primitives when hypothesis is
absent (the ``_hyp`` shim turns ``@given`` tests into skips).
"""

import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

import jax.numpy as jnp

from repro.core import dpp

# Small sizes: pallas-interpret runs each kernel through the interpreter,
# and hypothesis multiplies examples — keep the product cheap.  No
# subnormals: XLA:CPU flushes them to zero, which no numpy oracle does.
_values = st.lists(
    st.floats(-100, 100, allow_nan=False, allow_subnormal=False, width=32),
    min_size=1, max_size=48,
)
_n_segments = st.integers(min_value=1, max_value=12)


def _segment_oracle(ids, vals, n, op):
    fill = {"add": 0.0, "min": np.inf, "max": -np.inf}[op]
    out = np.full(n, fill, np.float64)
    fn = {"add": np.add, "min": np.minimum, "max": np.maximum}[op]
    for i, v in zip(ids, vals):
        out[i] = fn(out[i], v)
    if op != "add":  # jax segment_min/max fill empty segments with +/-inf
        return out.astype(np.float32)
    return out.astype(np.float32)


# ---------------------------------------------------------------------------
# reduce_by_key: backend parity + oracle
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(_values, _n_segments, st.integers(0, 2**31 - 1), st.sampled_from(["add", "min"]))
def test_reduce_by_key_backend_parity(vals, n_seg, seed, op):
    """xla and pallas-interpret lowerings agree on random 1-D float inputs
    (the shapes/ops the one-hot kernel supports)."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, n_seg, len(vals)), jnp.int32)
    v = jnp.asarray(np.asarray(vals, np.float32))
    want = dpp.reduce_by_key(ids, v, n_seg, op=op, backend="xla")
    got = dpp.reduce_by_key(ids, v, n_seg, op=op, backend="pallas-interpret")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@settings(max_examples=30, deadline=None)
@given(_values, _n_segments, st.integers(0, 2**31 - 1), st.sampled_from(["add", "min", "max"]))
def test_reduce_by_key_matches_oracle(vals, n_seg, seed, op):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_seg, len(vals))
    got = dpp.reduce_by_key(
        jnp.asarray(ids, jnp.int32),
        jnp.asarray(np.asarray(vals, np.float32)),
        n_seg,
        op=op,
    )
    want = _segment_oracle(ids, np.asarray(vals, np.float32), n_seg, op)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


def test_reduce_by_key_backend_parity_pinned():
    """Example-based companion that runs without hypothesis."""
    vals = jnp.asarray(np.arange(24, dtype=np.float32) - 11.5)
    ids = jnp.asarray(np.arange(24, dtype=np.int32) % 5)
    for op in ("add", "min"):
        want = dpp.reduce_by_key(ids, vals, 5, op=op, backend="xla")
        got = dpp.reduce_by_key(ids, vals, 5, op=op, backend="pallas-interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# scans
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(_values, st.booleans(), st.sampled_from([np.float32, np.int32]))
def test_scan_matches_numpy(vals, exclusive, dtype):
    arr = np.asarray(vals).astype(dtype)
    got = np.asarray(dpp.scan_(jnp.asarray(arr), exclusive=exclusive))
    inc = np.cumsum(arr, dtype=dtype)
    want = inc - arr if exclusive else inc
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=24))
def test_counts_to_offsets_is_exclusive_scan_with_total(counts):
    c = np.asarray(counts, np.int32)
    off = np.asarray(dpp.counts_to_offsets(jnp.asarray(c)))
    assert off.shape == (len(c) + 1,)
    assert off[0] == 0 and off[-1] == c.sum()
    np.testing.assert_array_equal(np.diff(off), c)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=16), st.integers(0, 8))
def test_expand_with_rank_inverts_counts(counts, extra_pad):
    c = np.asarray(counts, np.int32)
    total = int(c.sum()) + extra_pad
    if total == 0:
        return
    src, rank = dpp.expand_with_rank(jnp.asarray(c), total)
    src, rank = np.asarray(src), np.asarray(rank)
    n = len(c)
    # valid lanes reconstruct counts exactly; padding lanes carry sentinel n
    for row in range(n):
        sel = src == row
        assert sel.sum() == c[row]
        np.testing.assert_array_equal(np.sort(rank[sel]), np.arange(c[row]))
    assert (src == n).sum() == extra_pad


# ---------------------------------------------------------------------------
# compound keys + sort
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.tuples(st.integers(0, 999), st.integers(0, 999)),
        min_size=1,
        max_size=48,
    )
)
def test_compound_key_roundtrip_and_sort_order(pairs):
    major = jnp.asarray([p[0] for p in pairs], jnp.int32)
    minor = jnp.asarray([p[1] for p in pairs], jnp.int32)
    span = 1000
    keys = dpp.compound_key(major, minor, span, major_span=span)
    # roundtrip: decode recovers the pair
    np.testing.assert_array_equal(np.asarray(keys) // span, np.asarray(major))
    np.testing.assert_array_equal(np.asarray(keys) % span, np.asarray(minor))
    # sorting by the packed key == lexicographic sort of the pairs
    (sorted_keys,) = dpp.sort_by_key(keys)
    got = [(int(k) // span, int(k) % span) for k in np.asarray(sorted_keys)]
    assert got == sorted(pairs)


def test_compound_key_overflow_guard():
    big = 1 << 17  # 2^17 * 2^17 > int32
    major = jnp.asarray([0], jnp.int32)
    minor = jnp.asarray([0], jnp.int32)
    import jax

    if jax.dtypes.canonicalize_dtype(jnp.int64) == jnp.int64:
        pytest.skip("x64 enabled: the packed space fits int64")
    with pytest.raises(OverflowError, match="compound_key space"):
        dpp.compound_key(major, minor, big, major_span=big)


@settings(max_examples=30, deadline=None)
@given(
    st.integers(min_value=2, max_value=8),
    st.integers(min_value=1, max_value=4000),
    st.integers(0, 2**31 - 1),
)
def test_compound_key_label_fold_roundtrips(n_labels, n_hoods, seed):
    """The K-ary key-space fold (DESIGN.md §13): packing (hood_id, label)
    with minor span K round-trips exactly for any K*(n_hoods+1) space that
    fits the enabled integer width — the documented bound for
    ``hood_label_counts``/``vote_labels``."""
    rng = np.random.default_rng(seed)
    hood = rng.integers(0, n_hoods + 1, 64)
    lab = rng.integers(0, n_labels, 64)
    keys = dpp.compound_key(
        jnp.asarray(hood, jnp.int32), jnp.asarray(lab, jnp.int32),
        n_labels, major_span=n_hoods + 1,
    )
    keys = np.asarray(keys)
    np.testing.assert_array_equal(keys // n_labels, hood)
    np.testing.assert_array_equal(keys % n_labels, lab)
    assert keys.max() <= (n_hoods + 1) * n_labels - 1


def test_compound_key_label_fold_overflow_guard():
    """Beyond the documented K * (n_hoods + 1) bound the fold must raise,
    never silently wrap (the guard K-ary sessions rely on)."""
    import jax

    if jax.dtypes.canonicalize_dtype(jnp.int64) == jnp.int64:
        pytest.skip("x64 enabled: the packed space fits int64")
    n_labels = 8
    too_many_hoods = (2**31 // n_labels) + 1
    hood = jnp.asarray([0], jnp.int32)
    lab = jnp.asarray([0], jnp.int32)
    with pytest.raises(OverflowError, match="compound_key space"):
        dpp.compound_key(hood, lab, n_labels, major_span=too_many_hoods)
    # the largest fitting space still packs fine
    ok_hoods = 2**31 // n_labels - 1
    dpp.compound_key(hood, lab, n_labels, major_span=ok_hoods)


def test_compound_key_label_fold_pinned():
    """Example-based companion that runs without hypothesis."""
    hood = jnp.asarray([0, 5, 11, 11], jnp.int32)
    lab = jnp.asarray([2, 0, 4, 1], jnp.int32)
    keys = np.asarray(dpp.compound_key(hood, lab, 5, major_span=12))
    np.testing.assert_array_equal(keys, [2, 25, 59, 56])


@settings(max_examples=30, deadline=None)
@given(_values)
def test_sort_by_key_sorts_and_carries_values_stably(vals):
    keys = jnp.asarray(np.asarray(vals, np.float32))
    payload = jnp.arange(len(vals), dtype=jnp.int32)
    sk, sv = dpp.sort_by_key(keys, payload)
    sk, sv = np.asarray(sk), np.asarray(sv)
    assert (np.diff(sk) >= 0).all()
    # stable: equal keys keep submission order; payload is a permutation
    np.testing.assert_array_equal(np.sort(sv), np.arange(len(vals)))
    want = np.asarray(sorted(range(len(vals)), key=lambda i: (vals[i], i)))
    np.testing.assert_array_equal(sv, want)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(-50, 50), min_size=1, max_size=48))
def test_unique_matches_numpy_on_sorted_input(vals):
    arr = np.sort(np.asarray(vals, np.int32))
    uniq, count = dpp.unique_(jnp.asarray(arr), fill=-999)
    uniq, count = np.asarray(uniq), int(count)
    want = np.unique(arr)
    assert count == len(want)
    np.testing.assert_array_equal(uniq[:count], want)
    assert (uniq[count:] == -999).all()


# ---------------------------------------------------------------------------
# ticked pool-form parity under random admission orders (DESIGN.md §12/§13)
# ---------------------------------------------------------------------------

_pool_fixture = {}


def _pool_setup():
    """Lazily-built shared fixture: one session, three small plans, and the
    serial per-(rid, seed) reference results (memoized)."""
    if _pool_fixture:
        return _pool_fixture
    import jax  # noqa: F401  (ensure jax initialized before building plans)

    from repro import api
    from repro.core import synthetic

    sess = api.Segmenter(api.ExecutionConfig(overseg_grid=(6, 6)))
    vol = synthetic.make_synthetic_volume(seed=9, n_slices=3, shape=(40, 40))
    plans = [sess.plan(np.asarray(im)) for im in vol.images]
    bucket = api.BucketKey(
        *(max(p.bucket[d] for p in plans) for d in range(3))
    )
    _pool_fixture.update(
        session=sess, plans=plans, bucket=bucket, serial={}
    )
    return _pool_fixture


def _serial_result(rid, seed):
    fx = _pool_setup()
    key = (rid, seed)
    if key not in fx["serial"]:
        fx["serial"][key] = fx["session"].execute(
            fx["plans"][rid], seed=seed, bucket=fx["bucket"]
        )
    return fx["serial"][key]


def _run_pool(order, seeds, tick_iters=3):
    """Drive the requests through a 2-slot continuous-batching engine in the
    given admission order; returns completions keyed by rid."""
    from repro.serving import SegmentationEngine

    fx = _pool_setup()
    eng = SegmentationEngine(
        fx["session"], max_batch=2, tick_iters=tick_iters, bucket=fx["bucket"]
    )
    for rid in order:
        eng.submit(fx["plans"][rid], rid=rid, seed=seeds[rid])
    return {c.rid: c for c in eng.run()}


def _assert_pool_matches_serial(order, seeds):
    comps = _run_pool(order, seeds)
    assert sorted(comps) == sorted(order)
    for rid in order:
        want = _serial_result(rid, seeds[rid])
        got = comps[rid].result
        np.testing.assert_array_equal(
            got.region_labels, want.region_labels,
            err_msg=f"rid={rid} order={order} seeds={seeds}",
        )
        np.testing.assert_array_equal(got.mu, want.mu)
        np.testing.assert_array_equal(got.sigma, want.sigma)
        assert got.em_iters == want.em_iters
        assert got.map_iters == want.map_iters


@pytest.mark.slow  # several full ticked-pool runs; the pinned companion
# below keeps one admission-order parity case in the fast tier
@settings(max_examples=4, deadline=None)
@given(
    st.permutations([0, 1, 2]),
    st.tuples(*(st.integers(0, 2) for _ in range(3))),
)
def test_ticked_pool_parity_under_random_admission(order, seeds):
    """Every lane of the flat ticked pool reproduces serial ``run_em``
    bitwise in all label-visible outputs, regardless of which requests
    share the pool, in what order they are admitted, and which init seeds
    they carry (the continuous-batching contract, DESIGN.md §12)."""
    _assert_pool_matches_serial(list(order), list(seeds))


def test_ticked_pool_parity_pinned():
    """Example-based companion that runs without hypothesis."""
    _assert_pool_matches_serial([2, 0, 1], [1, 0, 2])


# ---------------------------------------------------------------------------
# segmented_scan: a lane-by-lane oracle on integer-valued floats
# ---------------------------------------------------------------------------


def _segscan_oracle(vals, starts, op, reverse):
    out = np.empty_like(vals)
    n = vals.shape[0]
    acc = None
    for i in (range(n - 1, -1, -1) if reverse else range(n)):
        first = i == (n - 1 if reverse else 0) or starts[i + 1 if reverse else i]
        acc = vals[i] if first else (acc + vals[i] if op == "add" else acc)
        out[i] = acc
    return out


def _check_segmented_scan(n, seed, density, op, reverse):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 8, n).astype(np.float32)
    starts = rng.random(n) < density
    got = dpp.segmented_scan(jnp.asarray(vals), jnp.asarray(starts), op,
                             reverse=reverse)
    np.testing.assert_array_equal(
        np.asarray(got), _segscan_oracle(vals, starts, op, reverse))


# A few lengths, so each draw reuses a compiled program: one row, exactly
# one row, a row and a lane, several rows.
@settings(max_examples=30, deadline=None)
@given(
    st.sampled_from([5, 1024, 1025, 2900]),
    st.integers(0, 2**31 - 1),
    st.sampled_from([0.0, 0.001, 0.05, 0.5, 1.0]),
    st.sampled_from(["add", "copy"]),
    st.booleans(),
)
def test_segmented_scan_matches_lane_oracle(n, seed, density, op, reverse):
    _check_segmented_scan(n, seed, density, op, reverse)


@pytest.mark.parametrize("op,reverse", [("add", False), ("copy", True)])
def test_segmented_scan_matches_lane_oracle_pinned(op, reverse):
    """Example-based companion that runs without hypothesis."""
    _check_segmented_scan(2900, 1, 0.002, op, reverse)
