"""EM / MAP optimization driver (paper Alg. 2, lines 6-12).

Structure mirrors the paper: an outer EM loop (parameter estimation) wraps
an inner MAP loop (label inference).  Convergence bookkeeping follows
§3.2.2: a per-neighborhood energy-sum history over the previous L=3
iterations, with a neighborhood marked converged when the change falls
below 1e-4 (relative), and the global check reduced via Scan/Reduce.  The
paper observes EM converges within 20 iterations and fixes that count; we
keep 20 as the default cap and also stop early on the EM window check.

Everything here is jittable with static shapes; the execution ``mode``
("faithful" | "static" | "static-pallas") selects the per-iteration
primitive sequence (see ``energy.py``), and ``backend`` selects the kernel
lowering through the dispatch layer (``kernels/ops.py``, DESIGN.md §3).

There is exactly ONE driver (:func:`_em_driver`), parametrized by a
collective context (``collectives.ReduceCtx``, DESIGN.md §11): the four
cross-element touch points — per-hood label counts, per-hood energy sums,
the label-vote scatter, and the convergence AND — go through the context's
hooks.  On one device the first three are reductions over the plan's
sorted runs instead (``energy.map_step_runs``, DESIGN.md §13); the sharded
context keeps its keyed scatters.  :func:`run_em` binds the single-device context;
``distributed.run_em_sharded`` builds a sharded context and ``shard_map``s
the same driver, so multi-device execution is a parametrization, not a
fork.

``run_em_batched`` vmaps the whole driver over a stack of problems padded
to shared static shapes (DESIGN.md §9) — one trace, one XLA program for an
entire volume.  Its lockstep cost model (every lane pays the slowest
lane's iteration count) is what ``run_em_ticked`` exists to fix: the
nested loops flattened into a per-lane state machine (:class:`TickState`)
advanced in fixed-size masked ticks, so a serving engine can retire
converged lanes and admit new requests between ticks (DESIGN.md §12).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import budget as budget_mod
from repro.core import dpp
from repro.core.pmrf import collectives
from repro.core.pmrf import energy as E
from repro.core.pmrf.hoods import Hoods
from repro.kernels import ops as kops

Array = jax.Array

CONV_TOL = 1.0e-4
WINDOW = 3  # the paper's L

MODES = ("faithful", "static", "static-pallas")

# Per-lane health lattice (DESIGN.md §14).  Computed device-side at every
# EM boundary — no extra readbacks — and carried in ``EMResult.status`` /
# ``TickState.status``.  DIVERGED and DEGENERATE are terminal: a sick lane
# sets ``done`` and freezes bitwise exactly like a converged one, so the
# serving engine quarantines it through the ordinary retirement path.
# Priority (highest wins): DIVERGED > DEGENERATE > CONVERGED > MAX_ITERS.
STATUS_OK = 0          # still iterating (only seen mid-flight)
STATUS_CONVERGED = 1   # EM window converged
STATUS_MAX_ITERS = 2   # stopped at the EM iteration cap
STATUS_DIVERGED = 3    # non-finite energies or parameters
STATUS_DEGENERATE = 4  # empty real label with sigma pinned at sigma_min

STATUS_NAMES = {
    STATUS_OK: "running",
    STATUS_CONVERGED: "converged",
    STATUS_MAX_ITERS: "max_iters",
    STATUS_DIVERGED: "diverged",
    STATUS_DEGENERATE: "degenerate",
}

#: Statuses that mean "the result is a legitimate segmentation".
OK_STATUSES = frozenset({STATUS_CONVERGED, STATUS_MAX_ITERS})

# Python-side trace counters: incremented each time a driver's body is
# traced (never inside the compiled program).  Tests assert that the
# batched multi-slice path compiles exactly one program for a whole stack
# and that the session API's executable cache (repro.api, DESIGN.md §10)
# performs zero traces on a warm hit.  ``run_em_sharded`` counts traces of
# the shard_map'd driver (``distributed.py``).
#
# The dict IS the analysis ledger's "trace" section (same object, see
# repro.analysis.budget / DESIGN.md §15): incrementing it here is what
# the compile-budget sentinel measures, so the counters tests assert on
# and the budgets the auditor gates on can never drift apart.
TRACE_COUNTS = budget_mod.LEDGER.section(
    "trace", keys=("run_em", "run_em_batched", "run_em_sharded", "run_em_ticked")
)


# Which reductions a MAP iteration was traced with: over the plan's sorted
# runs (every single-device unfused route) or by keyed scatter (the
# sharded context, whose hoods span shards).  Bumped at trace time.
budget_mod.LEDGER.section("em", keys=("run_reduce_traces", "scatter_reduce_traces"))


def reset_trace_counts() -> None:
    """Zero all trace counters (test hook; resets the ledger section)."""
    budget_mod.LEDGER.reset("trace")


class EMConfig(NamedTuple):
    max_em_iters: int = 20
    max_map_iters: int = 10
    mode: str = "static"          # "faithful" | "static" | "static-pallas"
    beta: float = 0.75
    sigma_min: float = 2.0
    backend: str = "auto"         # kernel dispatch backend (kernels/ops.py)
    precision: str = "f32"        # fused-tick energy arithmetic: "f32" | "bf16"


PRECISIONS = ("f32", "bf16")


def _validate_config(config: EMConfig) -> None:
    if config.mode not in MODES:
        raise ValueError(f"unknown mode {config.mode!r}; have {MODES}")
    if config.precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {config.precision!r}; have {PRECISIONS}"
        )
    if config.precision == "bf16" and config.mode != "static-pallas":
        raise ValueError(
            "precision='bf16' is a fused-tick feature: it requires "
            f"mode='static-pallas', got mode={config.mode!r}"
        )


class EMResult(NamedTuple):
    labels: Array        # (V+1,) int32 (sentinel lane 0)
    mu: Array            # (K,)
    sigma: Array         # (K,)
    hood_energy: Array   # (n_hoods,) final per-neighborhood energy sums
    total_energy: Array  # scalar
    em_iters: Array      # scalar int32
    map_iters: Array     # scalar int32 — total inner iterations executed
    status: Array        # scalar int32 — STATUS_* health code


class _MapCarry(NamedTuple):
    labels: Array
    hist: Array          # (WINDOW+1, n_hoods) ring of hood energy sums
    hood_energy: Array
    i: Array
    done: Array          # replicated convergence flag (ctx.all_converged)
    diverged: Array      # replicated non-finite-energy flag (folds into done)
    msums: Array         # (3, K) fused-tick M-step accumulators (sum_w /
                         # sum_wy / sum_wyy); zeros on the unfused routes


class _EmCarry(NamedTuple):
    labels: Array
    mu: Array
    sigma: Array
    hood_energy: Array
    total_hist: Array    # (WINDOW+1,) ring of total energies
    em_i: Array
    map_total: Array
    done: Array
    status: Array        # () int32 — STATUS_* at the last EM boundary


def init_params(
    key: Array, n_regions: int, n_labels: int = 2
) -> tuple[Array, Array, Array]:
    """Paper init: labels and per-label (mu, sigma) random in [0, 255]."""
    k1, k2, k3 = jax.random.split(key, 3)
    labels = jax.random.randint(k1, (n_regions + 1,), 0, n_labels).astype(jnp.int32)
    labels = labels.at[n_regions].set(0)
    mu = jnp.sort(jax.random.uniform(k2, (n_labels,), minval=0.0, maxval=255.0))
    sigma = jax.random.uniform(k3, (n_labels,), minval=10.0, maxval=80.0)
    return labels, mu.astype(jnp.float32), sigma.astype(jnp.float32)


def quantile_init(
    region_mean, n_regions: int, n_labels: int = 2
) -> tuple[Array, Array, Array]:
    """Data-driven init (beyond-paper option): mu at K quantiles spread
    over [q25, q75] (np.linspace pins the K=2 endpoints to the historical
    0.25/0.75 literals), labels by nearest mu (ties to the lowest label —
    the K=2 instance is bit-identical to the binary '<' rule)."""
    y = jnp.asarray(region_mean, jnp.float32)
    qs = np.linspace(0.25, 0.75, n_labels)
    mu = jnp.stack([jnp.quantile(y, float(q)) for q in qs])
    sigma = jnp.full((n_labels,), jnp.std(y) / 2.0 + 1.0, jnp.float32)
    labels = jnp.argmin(jnp.abs(y[:, None] - mu[None, :]), axis=1).astype(jnp.int32)
    labels = jnp.concatenate([labels, jnp.zeros((1,), jnp.int32)])
    return labels, mu.astype(jnp.float32), sigma


def _map_step(
    hoods: Hoods,
    model: E.EnergyModel,
    mode: str,
    backend: str,
    sctx: Optional[E.StaticMapContext],
    ctx: collectives.ReduceCtx,
    mu,
    sigma,
    carry: _MapCarry,
    *,
    active: Optional[Array] = None,
    precision: str = "f32",
) -> _MapCarry:
    """One MAP iteration.  ``active`` is the ticked driver's per-lane mask
    (DESIGN.md §12): it rides into every keyed-reduction touch point so a
    masked lane contributes exact zeros, and into the convergence AND so a
    masked lane reports converged.  ``active=None`` (the while_loop
    drivers) and ``active=True`` produce bitwise-identical results — the
    mask is a select, never an arithmetic rewrite.

    On the single-device static-pallas route the whole iteration — counts,
    energies, reductions, M-step accumulators, convergence predicate — is
    ONE fused launch (``E.em_tick_fused``, DESIGN.md §16) and the carry's
    ``msums`` holds the kernel's M-step sums for the EM boundary.  The
    sharded static-pallas route keeps ``E.map_step_fused`` (its collectives
    interleave with the kernel's stages).  The single-device static and
    faithful routes reduce over the plan's sorted runs
    (``E.map_step_runs``; every lane's reductions are its own, so they need
    no mask); the sharded ones by keyed scatter, psum'd."""
    n_labels = int(mu.shape[0])
    fused_tick = mode == "static-pallas" and not ctx.sharded
    conv_raw = None
    msums = carry.msums
    if fused_tick:
        labels, hood_e, conv_raw, sum_w, sum_wy, sum_wyy = E.em_tick_fused(
            hoods, model, sctx, carry.labels, mu, sigma, carry.hist,
            backend=backend, active=active, precision=precision,
            conv_tol=CONV_TOL,
        )
        msums = jnp.stack([sum_w, sum_wy, sum_wyy])
    elif mode == "static-pallas":
        budget_mod.LEDGER.bump("em", "scatter_reduce_traces")
        labels, hood_e = E.map_step_fused(
            hoods, model, sctx, carry.labels, mu, sigma, backend=backend, ctx=ctx,
            active=active,
        )
    elif not ctx.sharded:
        budget_mod.LEDGER.bump("em", "run_reduce_traces")
        labels, hood_e = E.map_step_runs(
            hoods, model, sctx, carry.labels, mu, sigma,
            faithful=mode == "faithful", backend=backend,
        )
    else:
        budget_mod.LEDGER.bump("em", "scatter_reduce_traces")
        # backend selects the keyed-reduction lowering here too; the vote
        # scatter stays on XLA (scatter_ has no pallas lowering).  The
        # neighborhood counts go through the collective context so sharded
        # runs see cross-shard context; per-element mins stay shard-local
        # (elements never straddle shards — only hoods do, via the counts).
        counts = E.hood_label_counts(
            hoods, carry.labels, n_labels, backend=backend, ctx=ctx, active=active
        )
        energies = E.label_energies(
            hoods, model, carry.labels, mu, sigma, hood_counts=counts,
            backend=backend,
        )
        if mode == "faithful":
            min_e, arg = E.min_energies_faithful(hoods, energies, backend=backend)
        else:
            min_e, arg = E.min_energies_static(energies)
        hood_e = E.hood_energy_sums(
            hoods, min_e, backend=backend, ctx=ctx, active=active
        )
        labels = E.vote_labels(
            hoods, arg, hoods.n_regions, n_labels, ctx=ctx, active=active
        )
    hist = jnp.roll(carry.hist, shift=1, axis=0).at[0].set(hood_e)
    i = carry.i + 1
    # Convergence is decided in the body (not the loop cond) so the
    # collective AND runs in replicated context on every backend.  The
    # fused tick already reduced the window predicate in-kernel (same
    # arithmetic as _window_converged on the post-roll ring); only the
    # iteration-count gate is applied here.
    if conv_raw is not None:
        conv = ctx.all_converged(
            jnp.where(i > WINDOW, conv_raw, False), active=active
        )
    else:
        conv = ctx.all_converged(_window_converged(hist, i), active=active)
    # Divergence folds into ``done`` so a poisoned lane exits the inner
    # loop *immediately* — detection and termination are atomic, which is
    # what lets the ticked drivers skip carrying the flag between steps.
    # ``hood_e`` is already replicated (it went through the collective
    # context), so a plain jnp.all sees the same value on every shard; a
    # masked (frozen) lane contributes exact zeros, which are finite.
    diverged = ~jnp.all(jnp.isfinite(hood_e))
    return _MapCarry(
        labels=labels, hist=hist, hood_energy=hood_e, i=i,
        done=conv | diverged, diverged=diverged, msums=msums,
    )


def _window_converged(hist: Array, i: Array) -> Array:
    """True where the last WINDOW deltas are all below tolerance (needs at
    least WINDOW+1 recorded iterations)."""
    deltas = jnp.abs(hist[:-1] - hist[1:])  # (WINDOW, ...)
    scale = jnp.maximum(jnp.abs(hist[0]), 1.0)
    conv = jnp.all(deltas < CONV_TOL * scale, axis=0)
    return jnp.where(i > WINDOW, conv, False)


def _degenerate_components(model: E.EnergyModel, sigma, sum_w) -> Array:
    """True when some *real* label ended the M-step with (near-)zero mass
    AND a reseed target pinned at ``sigma_min`` — it can never recapture
    mass (the collapsed-Gaussian hazard, DESIGN.md §14).  Inert padded
    labels (mixed-K pools, ``reseed_mu == INERT_MU``) are excluded: they
    are *supposed* to be empty.  A dead label whose reseed sigma exceeds
    ``sigma_min`` is the documented recovery path, not a degeneracy."""
    dead = sum_w < 1e-3 * jnp.sum(sum_w)
    real = model.reseed_mu < E.INERT_MU
    return jnp.any(dead & real & (sigma <= model.sigma_min))


def _boundary_status(div, deg, finished, em_conv, em_i, max_em_iters) -> Array:
    """STATUS_* code at one EM boundary (elementwise; works batched).

    DIVERGED dominates; DEGENERATE only sticks on a lane that is
    *finishing* this boundary (mid-run label death followed by reseed
    recovery is healthy); otherwise the ordinary converged / iteration-cap
    / still-running resolution."""
    i32 = jnp.int32
    return jnp.where(
        div,
        i32(STATUS_DIVERGED),
        jnp.where(
            finished & deg,
            i32(STATUS_DEGENERATE),
            jnp.where(
                em_conv,
                i32(STATUS_CONVERGED),
                jnp.where(
                    em_i >= max_em_iters, i32(STATUS_MAX_ITERS), i32(STATUS_OK)
                ),
            ),
        ),
    )


def _em_driver(
    hoods: Hoods,
    model: E.EnergyModel,
    labels0: Array,
    mu0: Array,
    sigma0: Array,
    config: EMConfig,
    ctx: collectives.ReduceCtx,
) -> EMResult:
    """THE EM driver — single-device and sharded execution both trace this
    exact function; only the collective context differs (module docstring).

    When ``ctx`` is sharded, ``hoods`` is the shard-local element block
    (with globally-indexed ``vertex``/``hood_id`` and shard-localized
    replication arrays — ``distributed.partition_hoods``), while
    ``model``/``labels0``/``mu0``/``sigma0`` are replicated.  All label and
    parameter state stays replicated across shards, so every shard takes
    the identical EM trajectory.
    """
    n_hoods = hoods.n_hoods
    mode = config.mode
    # Threaded raw; each layer resolves at trace time — "auto" follows
    # env/override/platform, and changing those after a trace is cached
    # will not retrace.
    kops.resolve_backend(config.backend)  # validate early: raises on unknown
    backend = config.backend
    # The sharded static and faithful routes hoist nothing: their keyed
    # scatters compute every reduction in the loop.
    sctx = (
        E.make_static_context(hoods, model, backend=backend, ctx=ctx)
        if mode == "static-pallas" or not ctx.sharded
        else None
    )

    fused_tick = mode == "static-pallas" and not ctx.sharded

    def map_loop(labels, mu, sigma):
        init = _MapCarry(
            labels=labels,
            hist=jnp.zeros((WINDOW + 1, n_hoods), jnp.float32),
            hood_energy=jnp.zeros((n_hoods,), jnp.float32),
            i=jnp.int32(0),
            done=jnp.bool_(False),
            diverged=jnp.bool_(False),
            msums=jnp.zeros((3, mu.shape[0]), jnp.float32),
        )

        def cond(c: _MapCarry):
            return (c.i < config.max_map_iters) & ~c.done

        return jax.lax.while_loop(
            cond,
            lambda c: _map_step(
                hoods, model, mode, backend, sctx, ctx, mu, sigma, c,
                precision=config.precision,
            ),
            init,
        )

    def em_body(c: _EmCarry) -> _EmCarry:
        mc = map_loop(c.labels, c.mu, c.sigma)
        if fused_tick:
            # The fused launch already accumulated the M-step sums for the
            # labels it produced; only the closed-form tail runs here.
            mu, sigma, sum_w = E.params_from_stats(
                model, mc.msums[0], mc.msums[1], mc.msums[2]
            )
        else:
            mu, sigma, sum_w = E.update_parameters_stats(model, mc.labels, mode)
        # Health classification (DESIGN.md §14) — pure extra compute on
        # values the boundary already produced; never rewrites the healthy
        # arithmetic, so healthy trajectories stay bitwise unchanged.
        div = (
            mc.diverged
            | ~jnp.all(jnp.isfinite(mu))
            | ~jnp.all(jnp.isfinite(sigma))
        )
        deg = _degenerate_components(model, sigma, sum_w)
        total = jnp.sum(mc.hood_energy)
        hist = jnp.roll(c.total_hist, 1).at[0].set(total)
        em_i = c.em_i + 1
        em_conv = ctx.all_converged(_window_converged(hist[:, None], em_i)[0])
        finished = div | ~((em_i < config.max_em_iters) & ~em_conv)
        return _EmCarry(
            labels=mc.labels,
            mu=mu,
            sigma=sigma,
            hood_energy=mc.hood_energy,
            total_hist=hist,
            em_i=em_i,
            map_total=c.map_total + mc.i,
            done=em_conv | div,
            status=_boundary_status(
                div, deg, finished, em_conv, em_i, config.max_em_iters
            ),
        )

    init = _EmCarry(
        labels=labels0,
        mu=mu0,
        sigma=sigma0,
        hood_energy=jnp.zeros((n_hoods,), jnp.float32),
        total_hist=jnp.zeros((WINDOW + 1,), jnp.float32),
        em_i=jnp.int32(0),
        map_total=jnp.int32(0),
        done=jnp.bool_(False),
        status=jnp.int32(STATUS_OK),
    )

    final = jax.lax.while_loop(
        lambda c: (c.em_i < config.max_em_iters) & ~c.done,
        em_body,
        init,
    )

    return EMResult(
        labels=final.labels,
        mu=final.mu,
        sigma=final.sigma,
        hood_energy=final.hood_energy,
        total_energy=jnp.sum(final.hood_energy),
        em_iters=final.em_i,
        map_iters=final.map_total,
        status=final.status,
    )


@partial(jax.jit, static_argnames=("config",))
def run_em(
    hoods: Hoods,
    model: E.EnergyModel,
    labels0: Array,
    mu0: Array,
    sigma0: Array,
    config: EMConfig = EMConfig(),
) -> EMResult:
    _validate_config(config)
    TRACE_COUNTS["run_em"] = TRACE_COUNTS.get("run_em", 0) + 1
    return _em_driver(hoods, model, labels0, mu0, sigma0, config, collectives.LOCAL)


@partial(jax.jit, static_argnames=("config",))
def run_em_batched(
    hoods: Hoods,
    model: E.EnergyModel,
    labels0: Array,
    mu0: Array,
    sigma0: Array,
    config: EMConfig = EMConfig(),
) -> EMResult:
    """Run EM over a stack of problems in one trace/compile (DESIGN.md §9).

    All array leaves carry a leading stack axis; the ``Hoods`` static
    fields must already be padded to shared values (``hoods.pad_hoods`` /
    ``energy.pad_model``).  The inner ``run_em`` call inlines into this
    trace, so the whole stack compiles exactly once; per-slice results are
    bit-identical to individual runs because padding lanes contribute
    exact zeros to every reduction.
    """
    TRACE_COUNTS["run_em_batched"] = TRACE_COUNTS.get("run_em_batched", 0) + 1

    def one(h, m, l0, u0, s0):
        return run_em(h, m, l0, u0, s0, config)

    return jax.vmap(one)(hoods, model, labels0, mu0, sigma0)


# ---------------------------------------------------------------------------
# Ticked EM: the continuous-batching serving driver (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# ``run_em_batched`` vmaps the *whole* while_loop, so a stack of problems
# advances in lockstep until the slowest lane converges — every lane pays
# the max iteration count.  The ticked
# driver flattens the nested EM/MAP while_loops into a single-micro-step
# state machine (:class:`TickState` + :func:`_tick_micro`) and advances a
# fixed pool of lanes by ``tick_iters`` masked micro-steps per call.
# Between calls the host retires converged lanes and admits new requests
# into the freed slots — continuous batching, with no retrace (the pool's
# shapes never change).  Each lane's trajectory is the exact micro-step
# sequence ``run_em`` executes, so per-request results are bit-identical
# to the serial driver (tested).


class TickState(NamedTuple):
    """Per-lane flattened EM/MAP machine state (one slot of the pool).

    The invariant between micro-steps is "inside the MAP loop, about to
    evaluate its cond": ``labels``/``mu``/``sigma`` are the EM-level
    parameters, ``map_hist``/``map_i``/``map_done`` the inner-loop carry,
    ``total_hist``/``em_i``/``map_total`` the outer-loop carry.  ``done``
    marks a lane whose ``run_em`` while-cond would be false — the micro
    step freezes such lanes bitwise (and an empty slot is just a lane born
    with ``done=True``).  Requires ``max_em_iters >= 1`` and
    ``max_map_iters >= 1`` (both loops always take their first step).
    """

    labels: Array       # (V+1,) int32
    mu: Array           # (K,) float32
    sigma: Array        # (K,) float32
    map_hist: Array     # (WINDOW+1, n_hoods) inner convergence ring
    map_i: Array        # () int32 — iterations in the current MAP loop
    map_done: Array     # () bool  — inner window converged
    hood_energy: Array  # (n_hoods,) most recent per-hood energy sums
    total_hist: Array   # (WINDOW+1,) outer convergence ring
    em_i: Array         # () int32
    map_total: Array    # () int32 — total inner iterations executed
    done: Array         # () bool  — lane finished (retire + refill me)
    status: Array       # () int32 — STATUS_* health code (DESIGN.md §14)


def init_tick_lane(labels0: Array, mu0: Array, sigma0: Array, n_hoods: int) -> TickState:
    """Fresh lane state for one admitted request (mirrors the while_loop
    drivers' init carries exactly)."""
    return TickState(
        labels=jnp.asarray(labels0, jnp.int32),
        mu=jnp.asarray(mu0, jnp.float32),
        sigma=jnp.asarray(sigma0, jnp.float32),
        map_hist=jnp.zeros((WINDOW + 1, n_hoods), jnp.float32),
        map_i=jnp.int32(0),
        map_done=jnp.bool_(False),
        hood_energy=jnp.zeros((n_hoods,), jnp.float32),
        total_hist=jnp.zeros((WINDOW + 1,), jnp.float32),
        em_i=jnp.int32(0),
        map_total=jnp.int32(0),
        done=jnp.bool_(False),
        status=jnp.int32(STATUS_OK),
    )


def blank_tick_state(
    batch: int, n_hoods: int, n_regions: int, n_labels: int = 2
) -> TickState:
    """An all-empty slot pool: every lane ``done`` (masked out) with benign
    parameter values (sigma=1 so even the discarded masked compute stays
    NaN-free)."""

    def full(shape, fill, dtype):
        return jnp.full((batch,) + shape, fill, dtype)

    return TickState(
        labels=full((n_regions + 1,), 0, jnp.int32),
        mu=full((n_labels,), 0.0, jnp.float32),
        sigma=full((n_labels,), 1.0, jnp.float32),
        map_hist=full((WINDOW + 1, n_hoods), 0.0, jnp.float32),
        map_i=full((), 0, jnp.int32),
        map_done=full((), False, jnp.bool_),
        hood_energy=full((n_hoods,), 0.0, jnp.float32),
        total_hist=full((WINDOW + 1,), 0.0, jnp.float32),
        em_i=full((), 0, jnp.int32),
        map_total=full((), 0, jnp.int32),
        done=full((), True, jnp.bool_),
        status=full((), STATUS_OK, jnp.int32),
    )


def tick_result(state: TickState) -> EMResult:
    """Read a finished lane (or a whole pool, with leading batch axes) out
    as the :class:`EMResult` ``run_em`` would have returned."""
    return EMResult(
        labels=state.labels,
        mu=state.mu,
        sigma=state.sigma,
        hood_energy=state.hood_energy,
        total_energy=jnp.sum(state.hood_energy, axis=-1),
        em_iters=state.em_i,
        map_iters=state.map_total,
        status=state.status,
    )


def _tick_micro(
    hoods: Hoods,
    model: E.EnergyModel,
    mode: str,
    backend: str,
    sctx: Optional[E.StaticMapContext],
    ctx: collectives.ReduceCtx,
    config: EMConfig,
    s: TickState,
) -> TickState:
    """One masked micro-step of the flattened EM/MAP machine (one lane).

    Executes exactly one MAP iteration; when that iteration exits the inner
    loop (window converged or iteration cap), the EM boundary work — the
    M-step, outer history/convergence, counter bookkeeping — is applied in
    the same step via selects, restoring the between-steps invariant.  A
    ``done`` lane is frozen bitwise.  The select structure never reorders
    the arithmetic ``run_em`` performs, so an N-micro-step trajectory here
    equals the serial driver's trajectory bit-for-bit.
    """
    active = ~s.done
    mc = _map_step(
        hoods, model, mode, backend, sctx, ctx, s.mu, s.sigma,
        _MapCarry(
            labels=s.labels, hist=s.map_hist, hood_energy=s.hood_energy,
            i=s.map_i, done=s.map_done, diverged=jnp.bool_(False),
            msums=jnp.zeros((3, s.mu.shape[0]), jnp.float32),
        ),
        active=active,
        precision=config.precision,
    )
    # Would the inner while_loop take another step?  (run_em's map cond.)
    # Divergence is already folded into mc.done, so a poisoned lane hits
    # the EM boundary in this same micro-step — identical sequencing to
    # the serial driver's while_loop exit.
    map_exit = ~((mc.i < config.max_map_iters) & ~mc.done)

    # EM boundary work, computed unconditionally and selected in: identical
    # values to run_em's em_body at the moment the inner loop exits.  The
    # fused-tick route's accumulators come straight from the launch — this
    # is what makes a lane-tick exactly one kernel boundary (DESIGN.md §16).
    if mode == "static-pallas" and not ctx.sharded:
        mu_b, sigma_b, sum_w_b = E.params_from_stats(
            model, mc.msums[0], mc.msums[1], mc.msums[2]
        )
    else:
        mu_b, sigma_b, sum_w_b = E.update_parameters_stats(model, mc.labels, mode)
    div_b = (
        mc.diverged
        | ~jnp.all(jnp.isfinite(mu_b))
        | ~jnp.all(jnp.isfinite(sigma_b))
    )
    deg_b = _degenerate_components(model, sigma_b, sum_w_b)
    total = jnp.sum(mc.hood_energy)
    hist_b = jnp.roll(s.total_hist, 1).at[0].set(total)
    em_i_b = s.em_i + 1
    em_done_b = ctx.all_converged(
        _window_converged(hist_b[:, None], em_i_b)[0], active=active
    )
    lane_done_b = div_b | ~((em_i_b < config.max_em_iters) & ~em_done_b)
    status_b = _boundary_status(
        div_b, deg_b, lane_done_b, em_done_b, em_i_b, config.max_em_iters
    )

    def sel(at_boundary, inside):
        return jnp.where(map_exit, at_boundary, inside)

    stepped = TickState(
        labels=mc.labels,
        mu=sel(mu_b, s.mu),
        sigma=sel(sigma_b, s.sigma),
        map_hist=sel(jnp.zeros_like(s.map_hist), mc.hist),
        map_i=sel(jnp.int32(0), mc.i),
        map_done=sel(jnp.bool_(False), mc.done),
        hood_energy=mc.hood_energy,
        total_hist=sel(hist_b, s.total_hist),
        em_i=sel(em_i_b, s.em_i),
        map_total=sel(s.map_total + mc.i, s.map_total),
        done=sel(lane_done_b, s.done),
        status=sel(status_b, s.status),
    )
    # Freeze retired / empty lanes bitwise (per-leaf select on s.done).
    return jax.tree.map(lambda new, old: jnp.where(s.done, old, new), stepped, s)


def _pool_tick_micro(
    hoods: Hoods,
    model: E.EnergyModel,
    sctx: E.StaticMapContext,
    backend: str,
    config: EMConfig,
    s: TickState,
) -> TickState:
    """One masked micro-step for the WHOLE pool (static mode's path).

    The MAP iteration is the serial driver's own (``E.map_step_runs``
    vmapped over the slots; its runs are lane-isolated and it scatters
    nothing), so every lane's labels and hood energies are bitwise the
    serial ones.  ``sctx`` is the slots' hoisted context, computed once per
    tick outside its loop.

    The EM boundary's M-step is flattened instead: ``jax.vmap`` of the
    per-lane label-keyed segment sums lowers to batched scatters, which
    XLA:CPU executes far worse than flat ones (measured ~3x per lane-step),
    so the slot axis folds into one lane-offset key space.  Arithmetic is a
    transcription of ``update_parameters`` + the `_tick_micro` boundary
    selects onto batched arrays; modes with per-lane sorts or kernel
    launches (faithful, static-pallas) keep the vmapped lane path.
    """
    budget_mod.LEDGER.bump("em", "run_reduce_traces")
    B = s.labels.shape[0]
    K = int(s.mu.shape[1])
    lane = jnp.arange(B, dtype=jnp.int32)
    active = ~s.done                                   # (B,)
    new_labels, hood_e = jax.vmap(
        lambda h, m, c, lab, mu, sigma: E.map_step_runs(
            h, m, c, lab, mu, sigma, backend=backend
        )
    )(hoods, model, sctx, s.labels, s.mu, s.sigma)

    map_hist = jnp.roll(s.map_hist, shift=1, axis=1).at[:, 0].set(hood_e)
    map_i = s.map_i + 1
    deltas = jnp.abs(map_hist[:, :-1] - map_hist[:, 1:])
    scale = jnp.maximum(jnp.abs(map_hist[:, 0]), 1.0)
    conv = jnp.all(deltas < CONV_TOL * scale[:, None], axis=1)     # (B, nh)
    # Divergence (== _map_step): non-finite lane energies exit the inner
    # loop this micro-step.  Lanes are isolated in every reduction (per-lane
    # runs, lane-offset key spaces), so one lane's NaN can never leak into
    # a co-resident healthy lane.
    bad = ~jnp.all(jnp.isfinite(hood_e), axis=1)                   # (B,)
    map_done = jnp.where(
        active,
        jnp.all(jnp.where(map_i[:, None] > WINDOW, conv, False), axis=1) | bad,
        jnp.bool_(True),
    )
    map_exit = ~((map_i < config.max_map_iters) & ~map_done)

    # --- EM boundary (== update_parameters static + em convergence) ----
    yv, wv = model.region_mean, model.region_weight
    seg_flat = (new_labels + lane[:, None] * K).reshape(-1)

    def seg_lab(vals):                                  # (B, V+1) -> (B, K)
        return dpp.reduce_by_key(
            seg_flat, vals.reshape(-1), B * K, op="add"
        ).reshape(B, K)

    sum_w = seg_lab(wv)
    sum_wy = seg_lab(wv * yv)
    sum_wyy = seg_lab(wv * yv * yv)
    safe_w = jnp.maximum(sum_w, 1e-6)
    mu_b = sum_wy / safe_w
    var = jnp.maximum(sum_wyy / safe_w - mu_b * mu_b, 0.0)
    sigma_b = jnp.maximum(jnp.sqrt(var), model.sigma_min[:, None])
    dead = sum_w < 1e-3 * jnp.sum(sum_w, axis=1, keepdims=True)
    mu_b = jnp.where(dead, model.reseed_mu, mu_b)
    sigma_b = jnp.where(dead, model.reseed_sigma[:, None], sigma_b)
    # Health classification (== _tick_micro's boundary, batched).
    div_b = (
        bad
        | ~jnp.all(jnp.isfinite(mu_b), axis=1)
        | ~jnp.all(jnp.isfinite(sigma_b), axis=1)
    )
    real = model.reseed_mu < E.INERT_MU                     # (B, K)
    deg_b = jnp.any(
        dead & real & (sigma_b <= model.sigma_min[:, None]), axis=1
    )

    total = jnp.sum(hood_e, axis=1)
    hist_b = jnp.roll(s.total_hist, shift=1, axis=1).at[:, 0].set(total)
    em_i_b = s.em_i + 1
    em_deltas = jnp.abs(hist_b[:, :-1] - hist_b[:, 1:])
    em_scale = jnp.maximum(jnp.abs(hist_b[:, 0]), 1.0)
    em_conv = jnp.all(em_deltas < CONV_TOL * em_scale[:, None], axis=1)
    em_done_b = jnp.where(
        active, jnp.where(em_i_b > WINDOW, em_conv, False), jnp.bool_(True)
    )
    lane_done_b = div_b | ~((em_i_b < config.max_em_iters) & ~em_done_b)
    status_b = _boundary_status(
        div_b, deg_b, lane_done_b, em_done_b, em_i_b, config.max_em_iters
    )

    def sel(at_boundary, inside):
        cond = map_exit
        if at_boundary.ndim > 1:
            cond = cond.reshape((B,) + (1,) * (at_boundary.ndim - 1))
        return jnp.where(cond, at_boundary, inside)

    stepped = TickState(
        labels=new_labels,
        mu=sel(mu_b, s.mu),
        sigma=sel(sigma_b, s.sigma),
        map_hist=sel(jnp.zeros_like(s.map_hist), map_hist),
        map_i=sel(jnp.zeros_like(map_i), map_i),
        map_done=sel(jnp.zeros_like(map_done), map_done),
        hood_energy=hood_e,
        total_hist=sel(hist_b, s.total_hist),
        em_i=sel(em_i_b, s.em_i),
        map_total=sel(s.map_total + map_i, s.map_total),
        done=sel(lane_done_b, s.done),
        status=sel(status_b, s.status),
    )

    def freeze(new, old):
        cond = s.done
        if new.ndim > 1:
            cond = cond.reshape((B,) + (1,) * (new.ndim - 1))
        return jnp.where(cond, old, new)

    return jax.tree.map(freeze, stepped, s)


@partial(jax.jit, static_argnames=("config", "tick_iters"))
def run_em_ticked(
    hoods: Hoods,
    model: E.EnergyModel,
    state: TickState,
    config: EMConfig = EMConfig(),
    tick_iters: int = 8,
) -> tuple[TickState, Array]:
    """Advance a slot pool by up to ``tick_iters`` masked micro-steps (one
    tick); returns ``(state, steps_executed)``.

    All inputs carry a leading slot axis (the pool's ``max_batch``); static
    ``Hoods`` fields must hold the pool's shared bucket values.  Lanes with
    ``state.done`` are frozen, so the host can retire them and write fresh
    requests into their slots between ticks without disturbing in-flight
    lanes — and without retracing, because the pool's shapes never change
    (``TRACE_COUNTS["run_em_ticked"]``-tested).

    The tick exits early once every lane is ``done`` (partial-tick exit):
    the remaining micro-steps would all be full-pool freezes — bitwise
    no-ops — so skipping them cannot change any state, but it returns
    control to the host at the next *convergence* boundary instead of the
    tick boundary.  That is what lets the serving engine retire converged
    lanes promptly even under large tick sizes, and ``steps_executed``
    (an int32 scalar, <= tick_iters) is how the engine's cost model and
    residency accounting stay honest about work actually issued.

    The per-lane trajectory reproduces :func:`run_em` exactly in every
    label-visible output (labels, mu, sigma, iteration counts — tested
    bitwise); per-hood energies agree to float-reduction tolerance
    (DESIGN.md §12).
    """
    _validate_config(config)
    if config.max_em_iters < 1 or config.max_map_iters < 1:
        raise ValueError("run_em_ticked requires max_em_iters/max_map_iters >= 1")
    if tick_iters < 1:
        raise ValueError(f"tick_iters must be >= 1, got {tick_iters}")
    TRACE_COUNTS["run_em_ticked"] = TRACE_COUNTS.get("run_em_ticked", 0) + 1
    kops.resolve_backend(config.backend)  # validate early: raises on unknown
    mode, backend = config.mode, config.backend

    # The slots' hoisted context, once per tick, outside its loop.
    sctx = jax.vmap(lambda h, m: E.make_static_context(h, m, backend=backend))(
        hoods, model
    )
    if mode == "static":
        # Pool form: the M-step flattened over the slot axis.
        def micro(st):
            return _pool_tick_micro(hoods, model, sctx, backend, config, st)
    else:
        # faithful / static-pallas: per-lane sorts and kernel launches
        # don't flatten across the slot axis — vmap the lane step.
        def lane(h, m, c, s):
            return _tick_micro(h, m, mode, backend, c, collectives.LOCAL, config, s)

        def micro(st):
            return jax.vmap(lane)(hoods, model, sctx, st)

    def cond(carry):
        i, st = carry
        return (i < tick_iters) & ~jnp.all(st.done)

    def body(carry):
        i, st = carry
        return i + 1, micro(st)

    steps, final = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
    return final, steps
