"""MRF energy model + MAP/EM inner computations (paper §3.2.2, Alg. 2).

The energy of assigning label ``l`` to hood element ``e`` (vertex v):

    E(e, l) = w_v * [ (y_v - mu_l)^2 / (2 sigma_l^2) + log(sigma_l) ]      (data)
            + beta * #{ u in hood(e), u != e : x_u != l }                  (smooth)

with y_v the region mean intensity (the paper's data term), w_v the region
pixel count normalized to unit mean (so beta is scale-free), and x the
current label field.  This is the standard PMRF likelihood+prior energy
([39]); the paper's Map step computes the deviation term, and the
smoothness enters through the neighborhood structure.

The label count K is a first-class axis (DESIGN.md §13): every function
here is K-ary, with K carried by the array shapes (``mu``/``sigma``/
``model.reseed_mu`` are ``(K,)``) rather than a separate argument —
two traces with different K never alias because their shapes differ.
The paper's binary PMRF is the K=2 instance, and the K=2 results are
bit-identical to the historical binary implementation: every K-ary
rewrite below only touches integer-valued quantities (counts, votes),
whose float arithmetic is exact, so argmins/votes/labels are unchanged.
On one device, per-hood label counts and label votes are K-1 passes over
the plan's sorted runs plus an exact complement (:func:`map_step_runs`);
the sharded route folds K into its keyed scatters via
``dpp.compound_key``, the key spaces widening by a factor of K.

Three execution modes (DESIGN.md §2, the baseline-vs-optimized axis):

* ``faithful`` — the paper's exact primitive sequence per MAP iteration:
  Gather replicated arrays (size 2|hoods|) -> Map energy -> SortByKey to
  make label pairs adjacent -> ReduceByKey(Min) -> ReduceByKey(Add).
* ``static``  — beyond-paper TPU mode: the neighborhood structure is
  EM-invariant, so the sort is hoisted out of the loop entirely; energies
  are laid out (K, H) and the per-element min is a reshape-free axis-min,
  the per-hood sums segmented scans over the plan's ``hood_id`` runs.
* ``static-pallas`` — the static mode taken to the kernel level
  (DESIGN.md §3): every EM-invariant quantity (neighborhood sizes, vote
  denominators, gathered region stats) is hoisted into a
  :class:`StaticMapContext`, and the per-iteration body collapses to one
  label-count segment reduction plus a single fused kernel launch
  (``kernels/map_step.py``) computing energies, per-element mins, per-hood
  energy sums, and label votes in one pass.

All modes compute identical labels (tested to exact equality on CPU).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dpp
from repro.core.pmrf.collectives import LOCAL, ReduceCtx
from repro.core.pmrf.hoods import Hoods
from repro.kernels import ops as kops

Array = jax.Array


class EnergyModel(NamedTuple):
    """Static per-problem arrays consumed by the EM loop.

    All gathers are sentinel-safe: region arrays are extended by one lane
    (index n_regions) holding zeros.
    """

    region_mean: Array   # (V+1,) float32, sentinel 0
    region_weight: Array # (V+1,) float32, unit-mean pixel counts, sentinel 0
    beta: Array          # scalar float32 smoothness weight
    sigma_min: Array     # scalar float32 lower bound on sigma
    reseed_mu: Array     # (K,) float32 — data quantiles spread over
                         # [q10, q90], used to re-seed a label whose
                         # cluster dies during EM (K=2: exactly [q10, q90])
    reseed_sigma: Array  # scalar float32

    @property
    def n_labels(self) -> int:
        """K, carried by the reseed array shape (DESIGN.md §13)."""
        return int(self.reseed_mu.shape[0])


def make_energy_model(
    region_mean,
    region_size,
    *,
    beta: float = 0.75,
    sigma_min: float = 2.0,
    n_labels: int = 2,
) -> EnergyModel:
    if n_labels < 2:
        raise ValueError(f"n_labels must be >= 2, got {n_labels}")
    y = jnp.asarray(region_mean, jnp.float32)
    mean = jnp.concatenate([y, jnp.zeros((1,), jnp.float32)])
    w = jnp.asarray(region_size, jnp.float32)
    w = w / jnp.maximum(jnp.mean(w), 1e-6)
    w = jnp.concatenate([w, jnp.zeros((1,), jnp.float32)])
    # Re-seed quantiles: np.linspace pins the endpoints exactly, so K=2
    # evaluates jnp.quantile at the same 0.10/0.90 literals as the
    # historical binary model (bit-identical reseed targets).
    qs = np.linspace(0.10, 0.90, n_labels)
    return EnergyModel(
        region_mean=mean,
        region_weight=w,
        beta=jnp.float32(beta),
        sigma_min=jnp.float32(sigma_min),
        reseed_mu=jnp.stack([jnp.quantile(y, float(q)) for q in qs]),
        reseed_sigma=jnp.maximum(jnp.std(y) / 2.0, sigma_min),
    )


def label_energies(
    hoods: Hoods,
    model: EnergyModel,
    labels: Array,
    mu: Array,
    sigma: Array,
    hood_counts: Tuple[Array, Array] | None = None,
    *,
    backend: Optional[str] = None,
) -> Array:
    """Energies for all K candidate labels, shape (K, H_pad).

    ``labels`` is (V+1,) int32 (sentinel lane ignored via zero weight) and
    K is carried by ``mu``/``sigma`` (both (K,)).  The Map DPP of the
    paper's "Compute Energy Function" step.

    ``hood_counts`` optionally supplies the per-(hood, label) count matrix
    and per-hood sizes — the unified driver passes counts computed through
    its collective context (:func:`hood_label_counts`) so sharded runs see
    globally psum-reduced neighborhood context.

    ``backend`` selects the keyed-reduction lowering (DESIGN.md §3).
    """
    n_labels = int(mu.shape[0])
    v = hoods.vertex
    y = model.region_mean[v]
    w = model.region_weight[v] * hoods.valid.astype(jnp.float32)
    x = labels[v]

    if hood_counts is None:
        counts, nall = hood_label_counts(hoods, labels, n_labels, backend=backend)
    else:
        counts, nall = hood_counts
    cnt_e = counts[hoods.hood_id]    # (H_pad, K)
    nall_e = nall[hoods.hood_id]
    return _energies(
        model, y, w, x, hoods.valid, [cnt_e[:, l] for l in range(n_labels)],
        nall_e, mu, sigma,
    )


def _energies(model, y, w, x, valid, cnt_e, nall_e, mu, sigma) -> Array:
    """(K, H_pad) energies from per-element operands: ``cnt_e[l]`` is the
    count of label l in the element's hood, ``nall_e`` the hood's size."""
    sig = jnp.maximum(sigma, model.sigma_min)

    # Disagreement counts are normalized by the number of *other* elements
    # in the neighborhood so beta is independent of hood size (hood sizes
    # vary wildly across datasets — the paper's §4.3.3 demographics).
    denom = jnp.maximum(nall_e - 1.0, 1.0)

    # #{u != e : x_u != l} = (|hood| - #{x_u = l}) - [x_e != l].  Every
    # operand is an integer-valued float (exact), so the K=2 instance is
    # bit-identical to the historical n1-based binary expressions.
    def label_energy(l: int) -> Array:
        d = (y - mu[l])
        data = w * (d * d / (2.0 * sig[l] * sig[l]) + jnp.log(sig[l]))
        eq = (x == l).astype(jnp.float32)
        others_diff = (nall_e - cnt_e[l]) - (1.0 - eq)
        return data + model.beta * jnp.maximum(others_diff, 0.0) / denom * valid

    return jnp.stack([label_energy(l) for l in range(int(mu.shape[0]))])


def hood_label_counts(
    hoods: Hoods,
    labels: Array,
    n_labels: int,
    *,
    backend: Optional[str] = None,
    ctx: ReduceCtx = LOCAL,
    active: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Per-(hood, label) counts + per-hood sizes — collective touch point 1.

    The label axis is folded into the existing keyed reduction via
    ``dpp.compound_key`` (key = hood_id * K + x), so one segment-sum over a
    K-times-wider key space replaces per-label reductions — no new scatter
    launches (DESIGN.md §13).  ``compound_key`` statically verifies the
    (n_hoods + 1) * K key space fits the enabled integer width.

    Counts are integer-valued floats, so the psum of per-shard partials is
    *exact* — energies, argmins, and therefore labels are bitwise equal to
    the single-device run.  Returns ``(counts, nall)`` with ``counts``
    shaped (n_hoods + 1, K) and ``nall`` (n_hoods + 1,).

    ``active`` is the ticked driver's per-lane mask (DESIGN.md §12): a
    retired lane's counts are exact zeros, a live lane's are bitwise
    unchanged (the mask is a select, never arithmetic).
    """
    x = labels[hoods.vertex]
    ones = hoods.valid.astype(jnp.float32)
    key = dpp.compound_key(
        hoods.hood_id, x, n_labels, major_span=hoods.n_hoods + 1
    )
    counts = ctx.segment_sum(
        key, ones, (hoods.n_hoods + 1) * n_labels, backend=backend, where=active
    ).reshape(hoods.n_hoods + 1, n_labels)
    nall = ctx.segment_sum(
        hoods.hood_id, ones, hoods.n_hoods + 1, backend=backend, where=active
    )
    return counts, nall


#: Data-term sentinel for inert (padded) labels — mixed-K pools
#: (DESIGN.md §13).  A label with mu = INERT_MU is ~1e8 intensity units
#: from any region mean, so its energy (~w * 1e15) can never win the
#: per-element argmin: it collects zero counts, zero votes, and zero mass
#: (re-seeding the dead label back to INERT_MU each M-step).  Real-label
#: arithmetic is untouched, so a K-padded lane's trajectory is bitwise the
#: natural-K trajectory.
INERT_MU = 1.0e8


def pad_model_labels(model: EnergyModel, n_labels: int) -> EnergyModel:
    """Extend the model's label axis to ``n_labels`` with inert labels
    (mixed-K serving, DESIGN.md §13): padded reseed targets carry
    :data:`INERT_MU` so a dead padded label re-seeds back to inertness."""
    cur = model.n_labels
    if n_labels < cur:
        raise ValueError(f"cannot shrink label axis from {cur} to {n_labels}")
    if n_labels == cur:
        return model
    pad = jnp.full((n_labels - cur,), INERT_MU, jnp.float32)
    return model._replace(reseed_mu=jnp.concatenate([model.reseed_mu, pad]))


def pad_params_labels(
    mu0: Array, sigma0: Array, n_labels: int
) -> Tuple[Array, Array]:
    """Extend initial (mu, sigma) to ``n_labels`` with inert labels (the
    companion of :func:`pad_model_labels` for a lane's initial params)."""
    cur = int(mu0.shape[0])
    if n_labels < cur:
        raise ValueError(f"cannot shrink label axis from {cur} to {n_labels}")
    if n_labels == cur:
        return mu0, sigma0
    mu = jnp.concatenate(
        [jnp.asarray(mu0, jnp.float32),
         jnp.full((n_labels - cur,), INERT_MU, jnp.float32)]
    )
    sigma = jnp.concatenate(
        [jnp.asarray(sigma0, jnp.float32),
         jnp.ones((n_labels - cur,), jnp.float32)]
    )
    return mu, sigma


def pad_model(model: EnergyModel, n_regions: int) -> EnergyModel:
    """Zero-extend the sentinel-extended region arrays to ``n_regions + 1``.

    Used by the batched multi-slice path (DESIGN.md §9): appended lanes
    have zero weight, so every weighted reduction is bit-identical to the
    unpadded model.
    """
    cur = model.region_mean.shape[0] - 1
    if n_regions < cur:
        raise ValueError(f"cannot shrink model from {cur} to {n_regions} regions")
    if n_regions == cur:
        return model
    z = jnp.zeros((n_regions - cur,), jnp.float32)
    return model._replace(
        region_mean=jnp.concatenate([model.region_mean, z]),
        region_weight=jnp.concatenate([model.region_weight, z]),
    )


# ---------------------------------------------------------------------------
# Per-element label minimization — the two unfused modes
# ---------------------------------------------------------------------------


def min_energies_static(energies: Array) -> Tuple[Array, Array]:
    """(min_energy, argmin_label) per hood element — axis-min, no sort."""
    min_e = jnp.min(energies, axis=0)
    arg = jnp.argmin(energies, axis=0).astype(jnp.int32)
    return min_e, arg


def min_energies_faithful(
    hoods: Hoods, energies: Array, *, backend: Optional[str] = None
) -> Tuple[Array, Array]:
    """Paper-faithful: replicate to K|hoods| lanes (Gather), SortByKey so
    each element's K label energies are adjacent, ReduceByKey(Min) per
    element.

    K=2 uses the precomputed memory-free replication arrays
    (oldIndex/testLabel — the paper's exact §3.2.2 layout, shard-localized
    by ``distributed.partition_hoods``); K>2 builds the equivalent
    replication at trace time from the (K, H) energy array.  Both feed the
    identical Sort + segmented-Min, and Min is order-independent, so the
    per-element results agree bitwise with the static axis-min.
    """
    n_labels = int(energies.shape[0])
    h_pad = hoods.capacity
    big = jnp.float32(3.4e38)
    if n_labels == 2:
        rep_e = energies[hoods.rep_test_label, hoods.rep_old_index]
        rep_e = jnp.where(hoods.rep_valid, rep_e, big)
        rep_key = jnp.where(
            hoods.rep_valid, hoods.rep_old_index, h_pad
        ).astype(jnp.int32)
    else:
        lane = jnp.arange(h_pad, dtype=jnp.int32)
        rep_key = jnp.tile(jnp.where(hoods.valid, lane, h_pad), n_labels)
        rep_e = jnp.where(hoods.valid[None, :], energies, big).reshape(-1)

    sk, se = dpp.sort_by_key(rep_key, rep_e)
    min_e = dpp.reduce_by_key(
        sk, se, h_pad + 1, op="min", indices_are_sorted=True, backend=backend
    )[:h_pad]
    min_e = jnp.where(hoods.valid, min_e, 0.0)
    # Recover the argmin label: the min equals at least one of the K label
    # energies; argmax of the match mask takes the first (ties resolve to
    # the lowest label, matching argmin semantics).
    arg = jnp.argmax(energies == min_e[None, :], axis=0).astype(jnp.int32)
    arg = jnp.where(hoods.valid, arg, 0)
    return min_e, arg


def hood_energy_sums(
    hoods: Hoods,
    min_e: Array,
    *,
    backend: Optional[str] = None,
    ctx: ReduceCtx = LOCAL,
    active: Optional[Array] = None,
) -> Array:
    """ReduceByKey(Add) of per-element min energies -> per-hood sums
    (collective touch point 2: psum'd across shards; ``active`` masks a
    retired lane's contribution to exact zero, DESIGN.md §12)."""
    return ctx.segment_sum(
        hoods.hood_id, jnp.where(hoods.valid, min_e, 0.0), hoods.n_hoods + 1,
        backend=backend, where=active,
    )[: hoods.n_hoods]


def vote_labels(
    hoods: Hoods,
    arg: Array,
    n_regions: int,
    n_labels: int,
    *,
    ctx: ReduceCtx = LOCAL,
    active: Optional[Array] = None,
) -> Array:
    """Update Output Labels (paper step 3's Scatter).

    Deterministic adaptation: a vertex can belong to several neighborhoods
    whose scatters race in the paper (it notes the resulting label noise in
    §4.2.2); we resolve by plurality vote.  The label axis folds into the
    vote scatter via ``dpp.compound_key`` (key = vertex * K + argmin), one
    Scatter(Add) into a (V+1)*K field, then argmax over the label axis
    (ties to the lowest label — for K=2 this is exactly the historical
    "strict majority picks 1" rule, since votes are integer-exact).
    Collective touch point 3: the vote field is psum'd across shards —
    integer votes make the cross-shard sum exact, so sharded label updates
    are bitwise identical to single-device.
    Returns (V+1,) labels with the sentinel lane forced to 0.

    ``active`` (touch point 3's per-lane mask, DESIGN.md §12) zeroes a
    retired lane's vote field; the caller discards the resulting all-zero
    labels, so stale votes can never leak into a live update.
    """
    key = dpp.compound_key(
        hoods.vertex, jnp.where(hoods.valid, arg, 0), n_labels,
        major_span=n_regions + 1,
    )
    votes = ctx.vote_scatter(
        hoods.valid.astype(jnp.float32),
        key,
        (n_regions + 1) * n_labels,
        where=active,
    ).reshape(n_regions + 1, n_labels)
    new = jnp.argmax(votes, axis=1).astype(jnp.int32)
    return new.at[n_regions].set(0)


# ---------------------------------------------------------------------------
# static-pallas mode: hoisted context + single fused launch per iteration
# ---------------------------------------------------------------------------


class StaticMapContext(NamedTuple):
    """EM-invariant per-element arrays hoisted out of the MAP loop.

    Everything here depends only on the neighborhood structure and the
    region statistics — not on the evolving labels — so it is computed once
    per ``run_em`` call instead of once per MAP iteration.  (The K-ary
    plurality vote needs no hoisted denominator: argmax over per-label
    vote counts replaced the binary votes1-vs-votes_all comparison.)

    The run fields describe the sorted runs the single-device MAP
    iteration reduces over (:func:`map_step_runs`): valid elements sit
    packed in ascending ``hood_id`` runs (``hoods.offsets``), and the
    plan's ``vote_perm``/``vote_bounds`` order them by vertex.
    """

    y: Array          # (H_pad,) gathered region mean per hood element
    w: Array          # (H_pad,) gathered region weight, 0 on padding
    validf: Array     # (H_pad,) 1.0/0.0 validity mask
    nall_e: Array     # (H_pad,) neighborhood size per element
    run_start: Array  # (H_pad,) bool: the lane starts a hood_id run
    hood_last: Array  # (n_hoods,) int32 lane of each hood's last element
    hood_full: Array  # (n_hoods,) bool: the hood has an element
    valid_perm: Array # (H_pad,) bool validity in vertex order
    votes_all: Array  # (V+1,) int32 valid elements per vertex


def make_static_context(
    hoods: Hoods,
    model: EnergyModel,
    *,
    backend: Optional[str] = None,
    ctx: ReduceCtx = LOCAL,
) -> StaticMapContext:
    v = hoods.vertex
    validf = hoods.valid.astype(jnp.float32)
    hid = hoods.hood_id
    run_start = jnp.concatenate([jnp.ones((1,), bool), hid[1:] != hid[:-1]])
    if ctx.sharded:
        # A hood may span shards: its size is a psum of shard partials.
        nall = ctx.segment_sum(hid, validf, hoods.n_hoods + 1, backend=backend)
        nall_e = nall[hid]
    else:
        nall_e = _run_totals(validf, run_start)
    ends = hoods.offsets[1:]
    valid_perm = hoods.valid[hoods.vote_perm]
    return StaticMapContext(
        y=model.region_mean[v],
        w=model.region_weight[v] * validf,
        validf=validf,
        nall_e=nall_e,
        run_start=run_start,
        hood_last=jnp.maximum(ends - 1, 0),
        hood_full=ends > hoods.offsets[:-1],
        valid_perm=valid_perm,
        votes_all=_vertex_run_sums(valid_perm, hoods.vote_bounds),
    )


def _run_totals(values: Array, run_start: Array) -> Array:
    """Each lane's run total: a forward segmented sum, then each run's last
    lane copied back over the run."""
    fwd = dpp.segmented_scan(values, run_start, "add")
    return dpp.segmented_scan(fwd, run_start, "copy", reverse=True)


def _vertex_run_sums(flags: Array, bounds: Array) -> Array:
    """Exact per-vertex counts of ``flags`` (bool, in vertex order): an
    integer scan read at the run bounds, so the order never matters."""
    cum = dpp.blocked_scan(flags.astype(jnp.int32))
    at = jnp.concatenate([jnp.zeros((1,), jnp.int32), cum])[bounds]
    return at[1:] - at[:-1]


def map_step_runs(
    hoods: Hoods,
    model: EnergyModel,
    sctx: StaticMapContext,
    labels: Array,
    mu: Array,
    sigma: Array,
    *,
    faithful: bool = False,
    backend: Optional[str] = None,
) -> Tuple[Array, Array]:
    """One single-device MAP iteration over the plan's sorted runs ->
    (new labels, per-hood energy sums).

    No element-wide scatter: hood-keyed sums are segmented scans over the
    ``hood_id`` runs, votes are integer run sums over the vertex order.
    Two element-wide gathers remain: ``labels[vertex]`` and the argmin
    permuted into vertex order.

    * Counts: K-1 passes of a forward segmented sum and a reverse copy of
      each run's total, and the complement ``nall - sum(rest)`` for label
      0.  Counts are integer-valued floats below 2**24, so every order
      gives the scatter's exact values and the energies bit for bit.
    * Hood energy sums restart at each run start (a difference of two
      prefix sums would cancel catastrophically) and are read at each
      hood's last lane.
    * Votes: K-1 integer run sums plus the complement of the hoisted
      totals; ``argmax`` ties go to the lowest label.
    """
    n_labels = int(mu.shape[0])
    x = labels[hoods.vertex]
    cnt_rest = [
        _run_totals(sctx.validf * (x == l), sctx.run_start)
        for l in range(1, n_labels)
    ]
    cnt_e = [sctx.nall_e - sum(cnt_rest)] + cnt_rest
    energies = _energies(
        model, sctx.y, sctx.w, x, hoods.valid, cnt_e, sctx.nall_e, mu, sigma
    )
    if faithful:
        min_e, arg = min_energies_faithful(hoods, energies, backend=backend)
    else:
        min_e, arg = min_energies_static(energies)
    sums = dpp.segmented_scan(
        jnp.where(hoods.valid, min_e, 0.0), sctx.run_start, "add"
    )
    hood_e = jnp.where(sctx.hood_full, sums[sctx.hood_last], 0.0)

    arg_v = arg[hoods.vote_perm]
    votes_rest = [
        _vertex_run_sums((arg_v == l) & sctx.valid_perm, hoods.vote_bounds)
        for l in range(n_labels - 1)
    ]
    votes = jnp.stack(votes_rest + [sctx.votes_all - sum(votes_rest)])
    new = jnp.argmax(votes, axis=0).astype(jnp.int32)
    sentinel = jnp.arange(hoods.n_regions + 1) == hoods.n_regions
    return jnp.where(sentinel, 0, new), hood_e


def map_step_fused(
    hoods: Hoods,
    model: EnergyModel,
    sctx: StaticMapContext,
    labels: Array,
    mu: Array,
    sigma: Array,
    *,
    backend: Optional[str] = None,
    ctx: ReduceCtx = LOCAL,
    active: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """One MAP iteration in static-pallas mode -> (new labels, hood sums).

    Per iteration this issues exactly one keyed reduction (the
    label-dependent per-(hood, label) count, K folded into the key space)
    plus one fused kernel launch; the unfused static mode issues
    segment-sums and a vote scatter on top of the elementwise energy graph.

    Under a sharded context the kernel runs unchanged per shard (its inputs
    are the shard's hood elements plus globally-reduced counts) and the
    collectives stay *outside* the launch: the pre-kernel count is a psum'd
    segment sum, the post-kernel hood sums and (K, V+1) vote field are
    psum'd partials.

    ``active`` applies the ticked driver's per-lane mask (DESIGN.md §12) to
    the kernel's keyed outputs: a retired lane's hood sums and votes are
    exact zeros, a live lane's are bitwise unchanged.
    """
    n_labels = int(mu.shape[0])
    x = labels[hoods.vertex]
    xf = x.astype(jnp.float32) * sctx.validf
    # The one keyed reduction outside the kernel: per-(hood, label) counts,
    # K folded into the key space (neighborhood sizes are hoisted in sctx).
    key = dpp.compound_key(
        hoods.hood_id, x, n_labels, major_span=hoods.n_hoods + 1
    )
    counts = ctx.segment_sum(
        key, sctx.validf, (hoods.n_hoods + 1) * n_labels, backend=backend,
        where=active,
    ).reshape(hoods.n_hoods + 1, n_labels)
    cnt_e = counts[hoods.hood_id].T  # (K, H_pad) — the kernel's label grid
    sig = jnp.maximum(sigma, model.sigma_min)
    _, _, hood_e, votes = kops.fused_map_step(
        sctx.y,
        sctx.w,
        cnt_e,
        sctx.nall_e,
        xf,
        sctx.validf,
        hoods.hood_id,
        hoods.vertex,
        mu,
        sig,
        model.beta,
        n_hoods=hoods.n_hoods,
        n_vertices=hoods.n_regions + 1,
        backend=backend,
    )
    if active is not None:
        hood_e = jnp.where(active, hood_e, 0.0)
        votes = jnp.where(active, votes, 0.0)
    hood_e = ctx.psum(hood_e)
    votes = ctx.psum(votes)
    new = jnp.argmax(votes, axis=0).astype(jnp.int32)
    return new.at[hoods.n_regions].set(0), hood_e


def em_tick_fused(
    hoods: Hoods,
    model: EnergyModel,
    sctx: StaticMapContext,
    labels: Array,
    mu: Array,
    sigma: Array,
    hist: Array,
    *,
    backend: Optional[str] = None,
    active: Optional[Array] = None,
    precision: str = "f32",
    conv_tol: float = 1.0e-4,
) -> Tuple[Array, Array, Array, Array, Array, Array]:
    """One whole EM tick in a single kernel launch (DESIGN.md §16).

    Unlike :func:`map_step_fused`, NO keyed reduction runs outside the
    launch: the per-(hood, label) counts, per-hood energy sums, label
    votes, M-step accumulators, and the convergence predicate over
    ``hist`` all happen inside ``kops.fused_em_tick``.  Single-device
    (LOCAL-context) route only — the sharded path keeps
    :func:`map_step_fused`, whose collectives sit between the count,
    hood-sum, and vote stages.

    ``hist`` is the MAP convergence ring *before* this iteration's roll;
    the returned ``conv`` is the window predicate on the post-roll ring
    (the ``i > WINDOW`` gate stays with the caller).  ``active`` masks a
    retired lane's hood sums to exact zeros (labels/params of masked
    lanes are frozen by the ticked driver's select, DESIGN.md §12).

    Returns ``(labels, hood_e, conv, sum_w, sum_wy, sum_wyy)``.
    """
    x = labels[hoods.vertex]
    xf = x.astype(jnp.float32) * sctx.validf
    sig = jnp.maximum(sigma, model.sigma_min)
    new_labels, hood_e, _votes, conv, sum_w, sum_wy, sum_wyy = kops.fused_em_tick(
        sctx.y,
        sctx.w,
        sctx.nall_e,
        xf,
        sctx.validf,
        hoods.hood_id,
        hoods.vertex,
        model.region_mean,
        model.region_weight,
        hist,
        mu,
        sig,
        model.beta,
        n_hoods=hoods.n_hoods,
        n_vertices=hoods.n_regions + 1,
        precision=precision,
        conv_tol=conv_tol,
        backend=backend,
    )
    if active is not None:
        hood_e = jnp.where(active, hood_e, 0.0)
    return new_labels, hood_e, conv, sum_w, sum_wy, sum_wyy


def update_parameters(
    model: EnergyModel, labels: Array, mode: str
) -> Tuple[Array, Array]:
    """M-step (paper step 4): per-label mu/sigma from weighted region stats.

    faithful mode groups regions by SortByKey(label) + segmented reduce;
    static mode uses labels directly as segment ids.  Identical math.
    K comes from the model's reseed array (DESIGN.md §13).
    """
    mu, sigma, _ = update_parameters_stats(model, labels, mode)
    return mu, sigma


def update_parameters_stats(
    model: EnergyModel, labels: Array, mode: str
) -> Tuple[Array, Array, Array]:
    """M-step plus its per-label mass vector ``sum_w``.

    The mass is a free byproduct of the reductions the M-step already
    performs; the ticked drivers' health classification (DESIGN.md §14)
    uses it to detect degenerate components — a *real* (non-inert) label
    with (near-)zero mass whose reseed target is itself pinned at
    ``sigma_min`` can never recapture mass, which is the classic collapsed-
    Gaussian hazard of EM.  Returns ``(mu, sigma, sum_w)``.
    """
    n_labels = model.n_labels
    y = model.region_mean
    w = model.region_weight  # sentinel lane has weight 0
    lab = labels

    if mode == "faithful":
        sk, sy, sw = dpp.sort_by_key(lab, y, w)
        seg = sk
        sorted_flag = True
    else:
        seg, sy, sw = lab, y, w
        sorted_flag = False

    sum_w = dpp.reduce_by_key(seg, sw, n_labels, op="add", indices_are_sorted=sorted_flag)
    sum_wy = dpp.reduce_by_key(seg, sw * sy, n_labels, op="add", indices_are_sorted=sorted_flag)
    sum_wyy = dpp.reduce_by_key(seg, sw * sy * sy, n_labels, op="add", indices_are_sorted=sorted_flag)
    return params_from_stats(model, sum_w, sum_wy, sum_wyy)


def params_from_stats(
    model: EnergyModel, sum_w: Array, sum_wy: Array, sum_wyy: Array
) -> Tuple[Array, Array, Array]:
    """The M-step's closed form from its three per-label accumulators.

    Split out of :func:`update_parameters_stats` so the fused-tick route
    (DESIGN.md §16) — whose kernel emits ``sum_w``/``sum_wy``/``sum_wyy``
    directly — finishes the M-step with the *identical* tail arithmetic
    (same op order, including the cluster-death reseed).
    """
    safe_w = jnp.maximum(sum_w, 1e-6)
    mu = sum_wy / safe_w
    var = jnp.maximum(sum_wyy / safe_w - mu * mu, 0.0)
    sigma = jnp.maximum(jnp.sqrt(var), model.sigma_min)

    # Cluster-death re-seeding (EM robustness adaptation, DESIGN.md §8):
    # a label that captured (almost) no mass is re-seeded at its data
    # quantile (label l -> the l-th of K quantiles spread over [q10, q90],
    # matching the sorted-mu initialization convention) instead of
    # collapsing to a degenerate Gaussian that can never recapture mass.
    dead = sum_w < 1e-3 * jnp.sum(sum_w)
    mu = jnp.where(dead, model.reseed_mu, mu)
    sigma = jnp.where(dead, model.reseed_sigma, sigma)
    return mu, sigma, sum_w
