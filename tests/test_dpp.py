"""Property + unit tests for the canonical DPP layer (repro.core.dpp).

Each primitive is checked against a dynamic-shape numpy oracle, per the
static-shape adaptation documented in DESIGN.md §2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import dpp


@pytest.fixture(autouse=True, scope="module")
def _x64_for_this_module():
    """These oracle checks intentionally run with x64 enabled — but only
    for THIS module.  The old import-time ``jax.config.update`` leaked the
    flag to the entire suite at collection (pytest imports every module up
    front), silently changing float behavior for everything that ran after
    collection — including the golden-oracle harness, whose fixtures pin
    the default-precision trajectory (DESIGN.md §13)."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)

small_ints = st.lists(st.integers(min_value=-50, max_value=50), min_size=1, max_size=64)
# No subnormals: XLA:CPU flushes them to zero (a backend property, not a
# primitive's), so a drawn subnormal fails any exact numpy oracle.
small_floats = st.lists(
    st.floats(
        min_value=-100, max_value=100, allow_nan=False, allow_subnormal=False,
        width=32,
    ),
    min_size=1,
    max_size=64,
)


@settings(max_examples=40, deadline=None)
@given(small_floats)
def test_scan_inclusive_matches_numpy(xs):
    x = jnp.asarray(xs, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dpp.scan_(x)), np.cumsum(np.asarray(xs, np.float32)), rtol=1e-5
    )


@settings(max_examples=40, deadline=None)
@given(small_floats)
def test_scan_exclusive_shifts(xs):
    x = jnp.asarray(xs, dtype=jnp.float32)
    inc = np.asarray(dpp.scan_(x))
    exc = np.asarray(dpp.scan_(x, exclusive=True))
    # atol covers XLA-CPU flush-to-zero on subnormal inputs (FTZ is backend
    # behaviour, not a primitive bug).
    np.testing.assert_allclose(
        exc + np.asarray(xs, np.float32), inc, rtol=1e-5, atol=1e-30
    )
    assert exc[0] == 0.0


@settings(max_examples=40, deadline=None)
@given(small_ints)
def test_sort_by_key_sorts_and_is_stable(keys):
    k = jnp.asarray(keys, dtype=jnp.int32)
    v = jnp.arange(len(keys), dtype=jnp.int32)
    sk, sv = dpp.sort_by_key(k, v)
    sk, sv = np.asarray(sk), np.asarray(sv)
    assert (np.diff(sk) >= 0).all()
    # stability: equal keys keep original order
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(sv, order)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.integers(min_value=0, max_value=7), min_size=1, max_size=64),
    st.data(),
)
def test_reduce_by_key_matches_groupby(seg, data):
    vals = data.draw(
        st.lists(
            st.floats(
                min_value=-10, max_value=10, allow_nan=False,
                allow_subnormal=False, width=32,
            ),
            min_size=len(seg),
            max_size=len(seg),
        )
    )
    s = jnp.asarray(seg, dtype=jnp.int32)
    v = jnp.asarray(vals, dtype=jnp.float32)
    got = np.asarray(dpp.reduce_by_key(s, v, 8, op="add"))
    want = np.zeros(8, np.float32)
    np.add.at(want, np.asarray(seg), np.asarray(vals, np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    got_min = np.asarray(dpp.reduce_by_key(s, v, 8, op="min"))
    for i in range(8):
        mask = np.asarray(seg) == i
        if mask.any():
            np.testing.assert_allclose(
                got_min[i], np.asarray(vals, np.float32)[mask].min(), rtol=1e-5
            )


@settings(max_examples=40, deadline=None)
@given(small_ints)
def test_unique_matches_numpy(keys):
    srt = jnp.sort(jnp.asarray(keys, dtype=jnp.int32))
    out, count = dpp.unique_(srt)
    out, count = np.asarray(out), int(count)
    want = np.unique(np.asarray(keys))
    assert count == len(want)
    np.testing.assert_array_equal(out[:count], want)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=5), min_size=1, max_size=32))
def test_expand_matches_repeat(counts):
    c = jnp.asarray(counts, dtype=jnp.int32)
    total = int(sum(counts)) + 3  # padded
    src = np.asarray(dpp.expand(c, total))
    want = np.repeat(np.arange(len(counts)), counts)
    np.testing.assert_array_equal(src[: len(want)], want)
    assert (src[len(want):] == len(counts)).all()  # sentinel padding


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=5), min_size=1, max_size=32))
def test_expand_with_rank(counts):
    c = jnp.asarray(counts, dtype=jnp.int32)
    total = int(sum(counts)) + 2
    src, rank = dpp.expand_with_rank(c, total)
    src, rank = np.asarray(src), np.asarray(rank)
    want_src = np.repeat(np.arange(len(counts)), counts)
    want_rank = np.concatenate([np.arange(k) for k in counts]) if sum(counts) else np.array([], int)
    np.testing.assert_array_equal(src[: len(want_src)], want_src)
    np.testing.assert_array_equal(rank[: len(want_rank)], want_rank)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.booleans(), min_size=1, max_size=64),
)
def test_select_flagged_compaction(flags):
    v = jnp.arange(len(flags), dtype=jnp.int32)
    packed, count = dpp.select_flagged(v, jnp.asarray(flags))
    packed, count = np.asarray(packed), int(count)
    want = np.arange(len(flags))[np.asarray(flags)]
    assert count == len(want)
    np.testing.assert_array_equal(packed[:count], want)


def test_scatter_modes():
    v = jnp.asarray([5.0, 3.0, 7.0, 1.0])
    idx = jnp.asarray([0, 1, 0, 1])
    np.testing.assert_allclose(
        np.asarray(dpp.scatter_(v, idx, 2, mode="add")), [12.0, 4.0]
    )
    np.testing.assert_allclose(
        np.asarray(dpp.scatter_(v, idx, 2, mode="min", fill=np.inf)), [5.0, 1.0]
    )
    np.testing.assert_allclose(
        np.asarray(dpp.scatter_(v, idx, 2, mode="max", fill=-np.inf)), [7.0, 3.0]
    )


def test_scatter_mask_drops():
    v = jnp.asarray([1.0, 2.0, 3.0])
    idx = jnp.asarray([0, 1, 2])
    mask = jnp.asarray([True, False, True])
    out = np.asarray(dpp.scatter_(v, idx, 3, mode="set", fill=-1.0, mask=mask))
    np.testing.assert_allclose(out, [1.0, -1.0, 3.0])


def test_compound_key_orders_lexicographically():
    major = jnp.asarray([1, 0, 1, 0], dtype=jnp.int32)
    minor = jnp.asarray([0, 5, 3, 2], dtype=jnp.int32)
    key = dpp.compound_key(major, minor, 10)
    (sk, si) = dpp.sort_by_key(key, jnp.arange(4, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(si), [3, 1, 0, 2])


def test_segments_from_sorted():
    keys = jnp.asarray([2, 2, 5, 5, 5, 9], dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(dpp.segments_from_sorted(keys)), [0, 0, 1, 1, 1, 2]
    )


def test_counts_to_offsets():
    counts = jnp.asarray([2, 0, 3], dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(dpp.counts_to_offsets(counts)), [0, 2, 2, 5])


def test_profiler_records_counts():
    with dpp.profiled() as prof:
        x = jnp.arange(8, dtype=jnp.float32)
        dpp.scan_(x)
        dpp.reduce_(x)
        dpp.reduce_(x, op="min")
    assert prof.counts()["Scan"] == 1
    assert prof.counts()["Reduce"] == 2
    assert all(t >= 0 for t in prof.totals().values())


def test_map_applies_function():
    x = jnp.asarray([1.0, 2.0])
    y = jnp.asarray([3.0, 4.0])
    np.testing.assert_allclose(np.asarray(dpp.map_(lambda a, b: a * b, x, y)), [3.0, 8.0])


def _segscan_loop(values, starts, op, reverse):
    """Lane-by-lane segmented scan in the values' own dtype (lane 0 always
    starts a run; backwards, a run starts at its last lane)."""
    v = np.asarray(values)
    s = np.asarray(starts, bool).copy()
    s[0] = True
    n = v.shape[0]
    out = np.empty_like(v)
    acc = None
    for i in (range(n - 1, -1, -1) if reverse else range(n)):
        first = (i == n - 1 or s[i + 1]) if reverse else s[i]
        if first:
            acc = v[i]
        elif op == "add":
            acc = acc + v[i]
        out[i] = acc
    return out


# (lanes, run starts): runs across the 1024-lane rows, a run longer than a
# row, a run per lane, a trailing run of padding lanes past a row's end,
# and a length below one row.
_SEGSCAN_LAYOUTS = {
    "straddles-rows": (3000, [0, 1000, 1030, 2047, 2049]),
    "run-longer-than-a-row": (2600, [0, 5]),
    "unit-runs": (1500, list(range(1500))),
    "padding-lanes": (1100, [0, 17, 600, 1050]),
    "below-one-row": (7, [0, 3, 4]),
}


def _segscan_case(layout, rng, values):
    n, starts = _SEGSCAN_LAYOUTS[layout]
    flags = np.zeros(n, bool)
    flags[starts] = True
    return values(rng, n), flags


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("op", ["add", "copy"])
@pytest.mark.parametrize("layout", sorted(_SEGSCAN_LAYOUTS))
def test_segmented_scan_bitwise_on_integer_valued_floats(layout, op, reverse):
    rng = np.random.default_rng(7)
    vals, flags = _segscan_case(
        layout, rng, lambda r, n: r.integers(0, 10, n).astype(np.float32))
    got = dpp.segmented_scan(jnp.asarray(vals), jnp.asarray(flags), op,
                             reverse=reverse)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got),
                                  _segscan_loop(vals, flags, op, reverse))


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("layout", sorted(_SEGSCAN_LAYOUTS))
def test_segmented_scan_float_sums_within_1e6(layout, reverse):
    rng = np.random.default_rng(11)
    vals, flags = _segscan_case(
        layout, rng, lambda r, n: r.uniform(0, 100, n).astype(np.float32))
    got = dpp.segmented_scan(jnp.asarray(vals), jnp.asarray(flags), "add",
                             reverse=reverse)
    want = _segscan_loop(vals.astype(np.float64), flags, "add", reverse)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_segmented_scan_reads_run_totals_with_empty_runs():
    # Hood-style runs from offsets: empty runs repeat an offset and start
    # nothing; padding lanes past the last offset form a run of their own.
    sizes = np.array([3, 0, 1030, 0, 0, 5, 1, 0])
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    n = int(offsets[-1]) + 900
    flags = np.zeros(n, bool)
    flags[offsets[:-1][sizes > 0]] = True
    flags[offsets[-1]] = True
    rng = np.random.default_rng(3)
    vals = rng.integers(0, 5, n).astype(np.float32)
    fwd = np.asarray(dpp.segmented_scan(jnp.asarray(vals), jnp.asarray(flags)))
    totals = np.where(sizes > 0, fwd[np.maximum(offsets[1:] - 1, 0)], 0)
    want = [vals[a:b].sum() for a, b in zip(offsets[:-1], offsets[1:])]
    np.testing.assert_array_equal(totals, want)
    back = np.asarray(dpp.segmented_scan(jnp.asarray(fwd), jnp.asarray(flags),
                                         "copy", reverse=True))
    for a, b in zip(offsets[:-1], offsets[1:]):
        np.testing.assert_array_equal(back[a:b], vals[a:b].sum())


def test_segmented_scan_batched_rows_match_unbatched_bitwise():
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.uniform(0, 100, (3, 2500)).astype(np.float32))
    flags = jnp.asarray(rng.random((3, 2500)) < 0.002)
    batched = jax.vmap(lambda v, f: dpp.segmented_scan(v, f))(vals, flags)
    for row in range(3):
        np.testing.assert_array_equal(
            np.asarray(batched[row]),
            np.asarray(dpp.segmented_scan(vals[row], flags[row])))


def test_segmented_scan_rejects_unknown_op():
    with pytest.raises(ValueError, match="segmented_scan op"):
        dpp.segmented_scan(jnp.zeros(4), jnp.zeros(4, bool), "max")
