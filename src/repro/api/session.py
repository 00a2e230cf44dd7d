"""Plan → compile → execute session API (DESIGN.md §10).

``Segmenter`` is the public entry point for all segmentation traffic.  It
splits the lifecycle into the three phases serving-scale systems use:

* :meth:`Segmenter.plan` — oversegmentation + region graph + cliques +
  neighborhoods (the paper's untimed init phase) plus bucket assignment:
  the problem's data-dependent static shapes are rounded up to a shared
  ``(capacity, n_hoods, n_regions)`` bucket.
* :meth:`Segmenter.compile` — ahead-of-time lower + compile of the EM
  driver for one bucket, cached by ``(capacity, n_hoods, n_regions,
  backend, mode, em limits, batch)`` so repeat traffic never retraces.
  Compilation needs only shapes (``jax.ShapeDtypeStruct``), never data.
* :meth:`Segmenter.execute` — pad a plan into its bucket and run the
  cached executable; zero traces on a warm cache.

``submit``/``drain`` add request micro-batching on top: concurrent
same-bucket requests coalesce into one vmapped ``run_em_batched`` launch
(one compile, one kernel stream for the whole group), generalizing what
``segment_volume`` used to hardcode for homogeneous slice stacks.

Results are bit-identical across all paths (direct, padded, batched):
padding lanes contribute exact zeros to every reduction and phantom hoods
converge trivially (DESIGN.md §9), so the executable cache is a pure
performance layer, never a semantics layer.
"""

from __future__ import annotations

import itertools
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.analysis import budget as budget_mod
from repro.api.config import ExecutionConfig
from repro.api.errors import FallbackError, PlanError
from repro.core.pmrf import distributed as distributed_mod
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as energy_mod
from repro.core.pmrf import pipeline as pipeline_mod
from repro.core.pmrf.hoods import Hoods, pad_hoods
from repro.testing import chaos as chaos_mod

Array = jax.Array

#: Plan ids: the request id the session's spans carry.
_PLAN_IDS = itertools.count()


class BucketKey(NamedTuple):
    """Shared static shapes a plan is padded to (the compile unit)."""

    capacity: int
    n_hoods: int
    n_regions: int


class ExecutableKey(NamedTuple):
    """Cache key for a compiled EM program.

    ``backend`` is the *resolved* concrete name (never "auto"), so the key
    pins the actual lowering.  ``batch`` is ``None`` for the unbatched
    executable or the group size for a vmapped one — a batch-of-8 program
    and a single-request program are distinct XLA executables.  ``shards``
    is the mesh-axis size the program was compiled for (1 = single-device):
    a sharded compile consumes partitioned inputs and emits an SPMD
    program, so it must never alias an unsharded one in the LRU cache.
    ``tick_iters`` is ``None`` for the run-to-convergence drivers or the
    per-call micro-step chunk for a ticked serving executable
    (:meth:`Segmenter.compile_ticked`, DESIGN.md §12) — a ticked program
    consumes pool state, not initial parameters, so it never aliases a
    ``run_em`` compile.  ``n_labels`` is the label count K (DESIGN.md §13):
    every label-indexed input shape depends on it, so a K=2 compile must
    never alias a K>2 one.  ``precision`` is the fused-tick energy
    precision (DESIGN.md §16): an f32 trace and a bf16 trace are different
    programs with identical input shapes, so the key must split them.
    """

    capacity: int
    n_hoods: int
    n_regions: int
    backend: str
    mode: str
    max_em_iters: int
    max_map_iters: int
    batch: Optional[int]
    shards: int
    tick_iters: Optional[int] = None
    n_labels: int = 2
    precision: str = "f32"


@dataclass
class Plan:
    """A planned (initialized + bucketed) segmentation problem.

    ``init_seconds`` is the whole ``plan`` span."""

    problem: pipeline_mod.Problem
    bucket: BucketKey
    init_seconds: float
    # The request id this plan's spans carry (``repro.obs``).
    id: int = field(default_factory=lambda: next(_PLAN_IDS), compare=False)
    # Padded-input memo keyed by (bucket, seed, init): repeat executes of
    # the same plan are pure device replays, not re-pads (see _pad_plan).
    _padded: dict = field(default_factory=dict, repr=False, compare=False)

    @property
    def n_regions(self) -> int:
        return self.problem.graph.n_regions


@dataclass
class Executable:
    """One AOT-compiled EM program for a bucket (and optional batch size).

    ``compile_seconds`` is the ``session.compile`` span of the miss that
    built it."""

    key: ExecutableKey
    compiled: object                 # jax.stages.Compiled
    em_config: em_mod.EMConfig
    compile_seconds: float

    def __call__(self, *inputs):
        return self.compiled(*inputs)


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    def as_dict(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses, "evictions": self.evictions}


class _Pending(NamedTuple):
    plan: Plan
    seed: int
    bucket: BucketKey


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _batch_pays(capacities: Sequence[int], platform: str) -> bool:
    """``segment_stack(batch="auto")``'s rule: coalesce a stack into one
    lockstep launch only on an accelerator (XLA:CPU runs vmapped lanes one
    after another, so the lockstep padding is pure overhead there) and only
    when every slice's capacity is within 2x of the smallest (one bucket,
    bounded padding waste)."""
    caps = list(capacities)
    return len(caps) > 1 and max(caps) <= 2 * min(caps) and platform != "cpu"


def _abstract_inputs(
    bucket: BucketKey, batch: Optional[int], shards: int = 1, n_labels: int = 2
):
    """ShapeDtypeStruct pytrees matching a bucket's padded runtime inputs.

    Must mirror exactly what ``_pad_plan`` produces (shapes, dtypes, and
    the ``Hoods`` static treedef — ``n_elements=-1`` is the shared "mixed"
    override) or the AOT executable will reject its own inputs.  For a
    sharded program the element capacity is rounded up so it divides into
    ``shards`` equal blocks (mirroring ``distributed.partition_hoods``).
    ``n_labels`` sizes the label-indexed leaves (DESIGN.md §13).
    """
    cap, nh, nr = bucket
    if shards > 1:
        cap = _round_up(cap, shards)

    def arr(shape, dtype):
        if batch is not None:
            shape = (batch,) + shape
        return jax.ShapeDtypeStruct(shape, dtype)

    hoods = Hoods(
        vertex=arr((cap,), jnp.int32),
        hood_id=arr((cap,), jnp.int32),
        valid=arr((cap,), jnp.bool_),
        sizes=arr((nh,), jnp.int32),
        offsets=arr((nh + 1,), jnp.int32),
        n_hoods=nh,
        n_regions=nr,
        n_elements=-1,
        rep_old_index=arr((2 * cap,), jnp.int32),
        rep_test_label=arr((2 * cap,), jnp.int32),
        rep_hood_id=arr((2 * cap,), jnp.int32),
        rep_valid=arr((2 * cap,), jnp.bool_),
        vote_perm=arr((cap,), jnp.int32),
        vote_bounds=arr((nr + 2,), jnp.int32),
    )
    model = energy_mod.EnergyModel(
        region_mean=arr((nr + 1,), jnp.float32),
        region_weight=arr((nr + 1,), jnp.float32),
        beta=arr((), jnp.float32),
        sigma_min=arr((), jnp.float32),
        reseed_mu=arr((n_labels,), jnp.float32),
        reseed_sigma=arr((), jnp.float32),
    )
    labels0 = arr((nr + 1,), jnp.int32)
    mu0 = arr((n_labels,), jnp.float32)
    sigma0 = arr((n_labels,), jnp.float32)
    return hoods, model, labels0, mu0, sigma0


def _abstract_tick_state(bucket: BucketKey, batch: int, n_labels: int = 2):
    """ShapeDtypeStruct pytree for a ticked pool's state (mirrors
    ``em.blank_tick_state`` exactly — the AOT program must accept the
    engine's live pool)."""
    _, nh, nr = bucket
    w = em_mod.WINDOW + 1

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct((batch,) + shape, dtype)

    return em_mod.TickState(
        labels=arr((nr + 1,), jnp.int32),
        mu=arr((n_labels,), jnp.float32),
        sigma=arr((n_labels,), jnp.float32),
        map_hist=arr((w, nh), jnp.float32),
        map_i=arr((), jnp.int32),
        map_done=arr((), jnp.bool_),
        hood_energy=arr((nh,), jnp.float32),
        total_hist=arr((w,), jnp.float32),
        em_i=arr((), jnp.int32),
        map_total=arr((), jnp.int32),
        done=arr((), jnp.bool_),
        status=arr((), jnp.int32),
    )


class Segmenter:
    """A segmentation session: one execution policy, one executable cache.

    Thread-unsafe by design (like a jax trace); share across requests, not
    across threads.  See module docstring for the lifecycle.
    """

    def __init__(self, config: ExecutionConfig = ExecutionConfig()):
        self.config = config
        self._cache: "OrderedDict[ExecutableKey, Executable]" = OrderedDict()
        self._pending: List[_Pending] = []
        self.stats = CacheStats()
        # Fallback bookkeeping (DESIGN.md §14): once a key's compile fails
        # over to the fallback backend, warm traffic for the original key
        # routes straight to the fallback executable — the broken compile
        # is never re-attempted inside this session.
        self._fallback_redirects: Dict[ExecutableKey, ExecutableKey] = {}
        self.fallback_events: List[Dict] = []

    # ------------------------------------------------------------------
    # phase 1: plan
    # ------------------------------------------------------------------

    def bucket_of(self, hoods: Hoods) -> BucketKey:
        """Round a problem's static dims up to the session's bucket grid."""
        c = self.config
        return BucketKey(
            capacity=_round_up(hoods.capacity, c.capacity_bucket),
            n_hoods=_round_up(hoods.n_hoods, c.segment_bucket),
            n_regions=_round_up(hoods.n_regions, c.segment_bucket),
        )

    def plan(self, image, *, oversegmentation=None) -> Plan:
        """Initialization phase (paper Alg. 2 lines 1-5) + bucket assignment.

        Rejects unusable images with :class:`~repro.api.errors.PlanError`
        before any planning work (DESIGN.md §14): a non-finite pixel would
        otherwise flow silently into the region statistics and poison the
        lane's first energy evaluation.
        """
        plan_id = next(_PLAN_IDS)
        with obs.span("plan", req=plan_id) as whole:
            img = np.asarray(image)
            if img.size == 0:
                raise PlanError(f"cannot plan a zero-element image (shape {img.shape})")
            if np.issubdtype(img.dtype, np.floating) and not np.isfinite(img).all():
                bad = int(np.size(img) - np.isfinite(img).sum())
                raise PlanError(
                    f"image contains {bad} non-finite pixel(s); segmentation "
                    "energies are undefined for NaN/Inf intensities"
                )
            problem = pipeline_mod.initialize(
                img,
                overseg_grid=self.config.overseg_grid,
                overseg_iters=self.config.overseg_iters,
                beta=self.config.beta,
                sigma_min=self.config.sigma_min,
                n_labels=self.config.n_labels,
                oversegmentation=oversegmentation,
                capacity_bucket=self.config.capacity_bucket,
                segment_bucket=self.config.segment_bucket,
            )
            bucket = self.bucket_of(problem.hoods)
        return Plan(
            problem=problem,
            bucket=bucket,
            init_seconds=whole.seconds,
            id=plan_id,
        )

    # ------------------------------------------------------------------
    # phase 2: compile (cached)
    # ------------------------------------------------------------------

    def _key_for(
        self,
        bucket: BucketKey,
        batch: Optional[int],
        tick_iters: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> ExecutableKey:
        c = self.config
        return ExecutableKey(
            capacity=bucket.capacity,
            n_hoods=bucket.n_hoods,
            n_regions=bucket.n_regions,
            backend=backend if backend is not None else c.resolved_backend(),
            mode=c.mode,
            max_em_iters=c.max_em_iters,
            max_map_iters=c.max_map_iters,
            batch=batch,
            shards=c.shards,
            tick_iters=tick_iters,
            n_labels=c.n_labels,
            precision=c.precision,
        )

    def mesh(self) -> Mesh:
        """The session's device mesh (``shards`` devices on ``mesh_axis``).

        Raises with an actionable message when the process has fewer
        devices than the config asks for — on CPU, virtual devices come
        from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
        """
        n = self.config.shards
        devices = jax.devices()
        if len(devices) < n:
            raise RuntimeError(
                f"ExecutionConfig(shards={n}) needs {n} devices but the "
                f"process has {len(devices)}; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                "before importing jax"
            )
        return Mesh(np.array(devices[:n]), (self.config.mesh_axis,))

    def _get_or_compile(self, key: ExecutableKey, build) -> Executable:
        """Shared cache front-end for every compile surface.

        ``build(backend) -> (compiled, em_config)`` performs the actual
        lower+compile for a concrete backend.  On compile failure the
        session applies ``config.fallback`` (DESIGN.md §14): same-backend
        retries with capped backoff, then one recompile on the fallback
        backend — cached under the *fallback's own* key (the key pins the
        resolved backend), with a redirect recorded so warm traffic for
        the original key lands on the fallback executable directly.
        """
        with obs.span("session.compile") as lookup:
            key = self._fallback_redirects.get(key, key)
            exe = self._cache.get(key)
            if exe is not None:
                self._cache.move_to_end(key)
                self.stats.hits += 1
                budget_mod.LEDGER.bump("compile", "warm_hit")
                return exe

            self.stats.misses += 1
            budget_mod.LEDGER.bump("compile", "lower_compile")
            compiled, em_config, used_key = self._build_with_policy(key, build)
        exe = Executable(
            key=used_key,
            compiled=compiled,
            em_config=em_config,
            compile_seconds=lookup.seconds,
        )
        self._cache[used_key] = exe
        while len(self._cache) > self.config.max_cached_executables:
            self._cache.popitem(last=False)
            self.stats.evictions += 1
        return exe

    def _build_with_policy(self, key: ExecutableKey, build):
        """Run ``build`` under the fallback policy; returns
        ``(compiled, em_config, key_actually_compiled)``."""
        policy = self.config.fallback
        delay = policy.backoff_s
        attempt = 0
        while True:
            try:
                compiled, em_config = build(key.backend)
                return compiled, em_config, key
            except Exception as e:  # noqa: BLE001 — classify, then re-raise
                if attempt < policy.max_retries:
                    attempt += 1
                    time.sleep(min(delay, policy.max_backoff_s))
                    delay *= 2
                    continue
                if not (policy.enabled and key.backend != policy.backend):
                    raise
                fb_key = key._replace(backend=policy.backend)
                self.fallback_events.append(
                    {
                        "stage": "compile",
                        "from": key.backend,
                        "to": policy.backend,
                        "error": repr(e),
                    }
                )
                warnings.warn(
                    f"compile on backend {key.backend!r} failed after "
                    f"{attempt} retr{'y' if attempt == 1 else 'ies'} ({e!r}); "
                    f"falling back to {policy.backend!r}",
                    RuntimeWarning,
                    stacklevel=3,
                )
                try:
                    compiled, em_config = build(policy.backend)
                except Exception as fb_e:
                    raise FallbackError(
                        f"compile failed on {key.backend!r} and on the "
                        f"fallback backend {policy.backend!r}"
                    ) from fb_e
                self._fallback_redirects[key] = fb_key
                return compiled, em_config, fb_key

    def compile(
        self,
        target: Union[Plan, BucketKey, Tuple[int, int, int]],
        *,
        batch: Optional[int] = None,
        backend: Optional[str] = None,
    ) -> Executable:
        """Return the compiled EM program for a bucket, compiling on miss.

        LRU-cached by :class:`ExecutableKey`; a hit performs zero traces
        (asserted by tests via ``em.TRACE_COUNTS``).  Eviction drops the
        least-recently-used executable once the cache exceeds
        ``config.max_cached_executables``.  When the session is sharded
        (``config.shards > 1``) the compiled program is the SPMD
        ``run_em_sharded`` driver over the session mesh.  ``backend``
        overrides the session's resolved backend (the execute-time
        fallback path uses it); compile failures go through the session's
        :class:`~repro.api.config.FallbackPolicy`.
        """
        bucket = BucketKey(*(target.bucket if isinstance(target, Plan) else target))
        shards = self.config.shards
        if batch is not None and shards > 1:
            raise ValueError(
                "micro-batched executables are not supported with shards > 1 "
                "(the mesh already parallelizes one request across devices); "
                "drain() runs sharded requests serially"
            )
        key = self._key_for(bucket, batch, backend=backend)

        def build(bk: str):
            chaos_mod.on_compile(bk)
            em_config = self.config.em_config(backend=bk)
            abstract = _abstract_inputs(bucket, batch, shards, self.config.n_labels)
            if shards > 1:
                compiled = distributed_mod.run_em_sharded.lower(
                    *abstract, config=em_config, mesh=self.mesh(),
                    axis=self.config.mesh_axis,
                ).compile()
            else:
                fn = em_mod.run_em if batch is None else em_mod.run_em_batched
                compiled = fn.lower(*abstract, em_config).compile()
            return compiled, em_config

        return self._get_or_compile(key, build)

    def compile_ticked(
        self,
        target: Union[Plan, BucketKey, Tuple[int, int, int]],
        *,
        batch: int,
        tick_iters: int = 8,
        backend: Optional[str] = None,
    ) -> Executable:
        """Compile (or fetch) the ticked serving executable for a bucket.

        The program is ``em.run_em_ticked`` over a ``batch``-slot pool:
        each call advances every non-``done`` lane by up to ``tick_iters``
        masked micro-steps (exiting early once the whole pool is done) and
        returns ``(new pool state, steps executed)``.  It shares the session
        LRU cache with the run-to-convergence executables (distinct
        ``ExecutableKey.tick_iters``) and performs zero traces on a warm
        hit.  The serving engine (``repro.serving``) is the intended
        caller; see DESIGN.md §12 for the slot/tick/masking contract.
        Compile failures go through the session's
        :class:`~repro.api.config.FallbackPolicy` (DESIGN.md §14).
        """
        bucket = BucketKey(*(target.bucket if isinstance(target, Plan) else target))
        if self.config.shards > 1:
            raise ValueError(
                "ticked serving executables are single-device (the pool's "
                "slot axis is the parallel axis); use shards=1"
            )
        if batch < 1 or tick_iters < 1:
            raise ValueError("compile_ticked needs batch >= 1 and tick_iters >= 1")
        key = self._key_for(bucket, batch, tick_iters=tick_iters, backend=backend)
        n_labels = self.config.n_labels

        def build(bk: str):
            chaos_mod.on_compile(bk)
            em_config = self.config.em_config(backend=bk)
            hoods_abs, model_abs, *_ = _abstract_inputs(bucket, batch, 1, n_labels)
            state_abs = _abstract_tick_state(bucket, batch, n_labels)
            compiled = em_mod.run_em_ticked.lower(
                hoods_abs, model_abs, state_abs, em_config, tick_iters
            ).compile()
            return compiled, em_config

        return self._get_or_compile(key, build)

    def ticked_pool(self, target, *, batch: int):
        """An all-empty slot pool for a ticked executable — ``(hoods,
        model, state)`` with blank (sentinel) hoods/model lanes and
        ``em.blank_tick_state`` (every lane ``done``, ready for admission).
        Shapes match :meth:`compile_ticked`'s abstract inputs exactly."""
        bucket = BucketKey(*(target.bucket if isinstance(target, Plan) else target))
        cap, nh, nr = bucket
        n_labels = self.config.n_labels

        def full(shape, fill, dtype):
            return jnp.full((batch,) + shape, fill, dtype)

        hoods = Hoods(
            vertex=full((cap,), nr, jnp.int32),
            hood_id=full((cap,), nh, jnp.int32),
            valid=full((cap,), False, jnp.bool_),
            sizes=full((nh,), 0, jnp.int32),
            offsets=full((nh + 1,), 0, jnp.int32),
            n_hoods=nh,
            n_regions=nr,
            n_elements=-1,
            rep_old_index=full((2 * cap,), cap - 1, jnp.int32),
            rep_test_label=full((2 * cap,), 0, jnp.int32),
            rep_hood_id=full((2 * cap,), nh, jnp.int32),
            rep_valid=full((2 * cap,), False, jnp.bool_),
            # every lane invalid: vertex order is lane order, all in the
            # sentinel region's run
            vote_perm=jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (batch, cap)),
            vote_bounds=jnp.broadcast_to(
                jnp.where(jnp.arange(nr + 2) == nr + 1, cap, 0).astype(jnp.int32),
                (batch, nr + 2),
            ),
        )
        model = energy_mod.EnergyModel(
            region_mean=full((nr + 1,), 0.0, jnp.float32),
            region_weight=full((nr + 1,), 0.0, jnp.float32),
            beta=full((), self.config.beta, jnp.float32),
            sigma_min=full((), 1.0, jnp.float32),
            reseed_mu=full((n_labels,), 0.0, jnp.float32),
            reseed_sigma=full((), 1.0, jnp.float32),
        )
        state = em_mod.blank_tick_state(batch, nh, nr, n_labels)
        return hoods, model, state

    def lane_inputs(
        self, plan: Plan, *, bucket: Optional[BucketKey] = None, seed: int = 0
    ):
        """One request's padded per-lane inputs for a ticked pool:
        ``(hoods, model, labels0, mu0, sigma0)`` — exactly the arrays the
        serial :meth:`execute` path feeds ``run_em``, so a lane's ticked
        trajectory reproduces the serial result (memoized per plan, like
        ``execute``'s padding)."""
        bucket = BucketKey(*bucket) if bucket is not None else plan.bucket
        return self._pad_plan(plan, bucket, seed)

    def lane_state(
        self, plan: Plan, *, bucket: Optional[BucketKey] = None, seed: int = 0
    ):
        """One request's admission-ready lane: ``(hoods, model,
        lane_state)``, i.e. :meth:`lane_inputs` with the per-lane
        :class:`em.TickState` already built.  Memoized per plan alongside
        the padding (§17): the initial lane state is a pure function of the
        padded inputs, so steady-state admission pays zero host-side
        recomputation for repeat traffic."""
        bucket = BucketKey(*bucket) if bucket is not None else plan.bucket
        h1, m1, lab0, mu0, sig0 = self._pad_plan(plan, bucket, seed)
        memo_key = (
            "lane", bucket, seed, self.config.init, self.config.shards,
            self.config.n_labels,
        )
        lane = plan._padded.get(memo_key)
        if lane is None:
            lane = plan._padded[memo_key] = em_mod.init_tick_lane(
                lab0, mu0, sig0, bucket.n_hoods
            )
        return h1, m1, lane

    def clear_cache(self) -> None:
        self._cache.clear()
        self._fallback_redirects.clear()

    @property
    def cache_keys(self) -> Tuple[ExecutableKey, ...]:
        return tuple(self._cache)

    # ------------------------------------------------------------------
    # phase 3: execute
    # ------------------------------------------------------------------

    def _pad_plan(self, plan: Plan, bucket: BucketKey, seed: int):
        """Pad one plan's runtime inputs into ``bucket`` (memoized on the
        plan, so warm repeat traffic pays zero host-side padding work).

        Initial parameters come from the plan's own (unpadded) statistics
        so the padded trajectory matches the natural-shape one exactly.

        A plan built with *fewer* labels than this session is label-padded
        with inert sentinel labels (``energy.pad_model_labels``,
        DESIGN.md §13): the extra labels can never win an argmin, so the
        real labels take the bitwise natural-K trajectory — this is what
        lets one ticked pool serve mixed-K traffic.  Plans with more
        labels than the session are rejected.

        Sharded sessions additionally partition the padded hoods
        (``distributed.partition_hoods``: capacity rounded to a shard
        multiple, replication arrays localized per element block) — also
        memoized, so warm sharded traffic pays zero host-side work.
        """
        n_labels = self.config.n_labels
        plan_labels = plan.problem.model.n_labels
        if plan_labels > n_labels:
            raise ValueError(
                f"plan has {plan_labels} labels but the session compiles "
                f"for n_labels={n_labels}; re-plan with a wider session"
            )
        memo_key = (
            bucket, seed, self.config.init, self.config.shards, n_labels
        )
        cached = plan._padded.get(memo_key)
        if cached is not None:
            return cached
        p = plan.problem
        cap, nh, nr = bucket
        # The padded (+partitioned) hoods/model depend only on the bucket,
        # shard count, and label axis — memoized separately so multi-seed
        # traffic pays the host-side padding/partitioning work once.
        hoods_key = ("hoods", bucket, self.config.shards, n_labels)
        padded = plan._padded.get(hoods_key)
        if padded is None:
            hoods = pad_hoods(
                p.hoods, capacity=cap, n_hoods=nh, n_regions=nr, n_elements=-1
            )
            if self.config.shards > 1:
                hoods = distributed_mod.partition_hoods(hoods, self.config.shards)
            model = energy_mod.pad_model(p.model, nr)
            model = energy_mod.pad_model_labels(model, n_labels)
            padded = plan._padded[hoods_key] = (hoods, model)
        hoods, model = padded
        labels0, mu0, sigma0 = pipeline_mod._initial_params(p, seed, self.config.init)
        mu0, sigma0 = energy_mod.pad_params_labels(mu0, sigma0, n_labels)
        lab = jnp.zeros((nr + 1,), jnp.int32)
        lab = lab.at[: p.graph.n_regions].set(labels0[: p.graph.n_regions])
        plan._padded[memo_key] = (hoods, model, lab, mu0, sigma0)
        return plan._padded[memo_key]

    def _run_with_retry(self, exe: Executable, inputs):
        """Invoke an executable under the fallback policy's same-backend
        transient retry (capped backoff)."""
        policy = self.config.fallback
        delay = policy.backoff_s
        attempt = 0
        while True:
            try:
                chaos_mod.on_execute(exe.key.backend)
                return exe(*inputs)
            except Exception:
                if attempt >= policy.max_retries:
                    raise
                attempt += 1
                time.sleep(min(delay, policy.max_backoff_s))
                delay *= 2

    def execute(
        self, plan: Plan, *, seed: int = 0, bucket: Optional[BucketKey] = None
    ) -> pipeline_mod.SegmentationResult:
        """Run one plan through its bucket's cached executable.

        Execute failures follow the same :class:`FallbackPolicy` as
        compiles (DESIGN.md §14): transient retries on the same
        executable, then one recompile+rerun on the fallback backend (the
        redirect is remembered, so subsequent traffic goes straight to
        the fallback executable).

        Spans, under ``session.execute`` (tagged with the plan's id):
        ``session.compile``, ``session.pad``, ``session.launch`` (until the
        asynchronous call returns), ``session.wait`` and
        ``session.assemble``; ``optimize_seconds`` is launch plus wait.
        """
        bucket = BucketKey(*bucket) if bucket is not None else plan.bucket
        with obs.span("session.execute", req=plan.id):
            exe = self.compile(bucket)
            with obs.span("session.pad"):
                inputs = self._pad_plan(plan, bucket, seed)
            with obs.span("session.launch") as launch:
                res = self._launch(exe, bucket, inputs)
            with obs.span("session.wait") as wait:
                jax.block_until_ready(res.labels)
            with obs.span("session.assemble"):
                return pipeline_mod._assemble_result(
                    plan.problem, res, plan.init_seconds, launch.seconds + wait.seconds
                )

    def _launch(self, exe: Executable, bucket: BucketKey, inputs):
        """Call ``exe`` under the fallback policy (see :meth:`execute`)."""
        policy = self.config.fallback
        try:
            return self._run_with_retry(exe, inputs)
        except Exception as e:
            if not (policy.enabled and exe.key.backend != policy.backend):
                raise
            self.fallback_events.append(
                {
                    "stage": "execute",
                    "from": exe.key.backend,
                    "to": policy.backend,
                    "error": repr(e),
                }
            )
            warnings.warn(
                f"execute on backend {exe.key.backend!r} failed ({e!r}); "
                f"retrying on fallback backend {policy.backend!r}",
                RuntimeWarning,
                stacklevel=3,
            )
            self._fallback_redirects[exe.key] = exe.key._replace(
                backend=policy.backend
            )
            exe = self.compile(bucket, backend=policy.backend)
            try:
                return self._run_with_retry(exe, inputs)
            except Exception as fb_e:
                raise FallbackError(
                    f"execute failed on {self.config.resolved_backend()!r} "
                    f"and on the fallback backend {policy.backend!r}"
                ) from fb_e

    def segment(self, image, *, seed: int = 0, oversegmentation=None):
        """Convenience: plan + execute in one call."""
        return self.execute(
            self.plan(image, oversegmentation=oversegmentation), seed=seed
        )

    # ------------------------------------------------------------------
    # micro-batching: submit / drain
    # ------------------------------------------------------------------

    def submit(
        self,
        image_or_plan,
        *,
        seed: int = 0,
        bucket: Optional[BucketKey] = None,
    ) -> int:
        """Enqueue a request; returns its ticket (index into ``drain()``).

        ``bucket`` overrides the plan's own bucket — callers coalescing a
        known-homogeneous group (e.g. a volume's slices) pass the group's
        joint bucket so every member lands in one launch.
        """
        plan = (
            image_or_plan
            if isinstance(image_or_plan, Plan)
            else self.plan(image_or_plan)
        )
        bucket = BucketKey(*bucket) if bucket is not None else plan.bucket
        self._pending.append(_Pending(plan=plan, seed=seed, bucket=bucket))
        return len(self._pending) - 1

    def pending(self) -> int:
        return len(self._pending)

    def drain(self) -> List[pipeline_mod.SegmentationResult]:
        """Execute all pending requests, coalescing same-bucket groups.

        Each group of n > 1 requests runs as ONE vmapped ``run_em_batched``
        launch through a batch-n executable (one compile per (bucket, n),
        reused across drains).  Results come back in submission order and
        are bit-identical to serial :meth:`execute` calls (§9 padding
        invariance).

        Sharded sessions (``config.shards > 1``) run every request through
        the sharded executable *serially*: one request already occupies the
        whole mesh, so cross-request vmap batching would multiply, not
        hide, the device footprint.
        """
        pending, self._pending = self._pending, []
        if not pending:
            return []
        groups: "OrderedDict[BucketKey, List[int]]" = OrderedDict()
        for i, req in enumerate(pending):
            groups.setdefault(req.bucket, []).append(i)

        results: List[Optional[pipeline_mod.SegmentationResult]] = [None] * len(pending)
        try:
            for bucket, members in groups.items():
                if len(members) == 1 or self.config.shards > 1:
                    for i in members:
                        results[i] = self.execute(
                            pending[i].plan, seed=pending[i].seed, bucket=bucket
                        )
                    continue
                ids = tuple(pending[i].plan.id for i in members)
                with obs.span("session.execute", req=ids):
                    exe = self.compile(bucket, batch=len(members))
                    with obs.span("session.pad"):
                        padded = [
                            self._pad_plan(pending[i].plan, bucket, pending[i].seed)
                            for i in members
                        ]
                        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *padded)
                    with obs.span("session.launch") as launch:
                        res = exe(*stacked)
                    with obs.span("session.wait") as wait:
                        jax.block_until_ready(res.labels)
                    opt_s = (launch.seconds + wait.seconds) / len(members)
                    with obs.span("session.assemble"):
                        for j, i in enumerate(members):
                            res_i = em_mod.EMResult(*(leaf[j] for leaf in res))
                            results[i] = pipeline_mod._assemble_result(
                                pending[i].plan.problem, res_i,
                                pending[i].plan.init_seconds, opt_s,
                            )
        except Exception:
            # One group failing (compile OOM, bad bucket override) must not
            # strand the others: re-queue every request that has no result
            # yet — in original order, ahead of anything submitted since —
            # so the caller can fix the cause and drain again.
            unprocessed = [
                pending[i] for i in range(len(pending)) if results[i] is None
            ]
            self._pending = unprocessed + self._pending
            raise
        return results  # type: ignore[return-value]

    # ------------------------------------------------------------------
    # stack helper (what segment_volume used to hardcode)
    # ------------------------------------------------------------------

    def segment_stack(
        self,
        images: Sequence,
        *,
        seed: int = 0,
        batch: str = "auto",
    ) -> Tuple[List[pipeline_mod.SegmentationResult], float]:
        """Segment a slice stack; returns (results, mean optimize seconds).

        ``batch="always"`` submits every slice under the stack's joint
        bucket (elementwise max) so the whole volume coalesces into one
        launch; ``"never"`` always runs serially.  ``"auto"`` batches by
        :func:`_batch_pays` (DESIGN.md §18): only on an accelerator, only
        unsharded, and only when every slice's capacity is within 2x of
        the smallest.
        """
        if batch not in ("auto", "always", "never"):
            raise ValueError(f"batch must be auto/always/never, got {batch!r}")
        if batch == "always" and self.config.shards > 1:
            # Same contract as compile(batch=...): an explicit batching
            # request is incompatible with a sharded session, loudly.
            # (batch="auto" degrades to serial execution silently — the
            # mesh already parallelizes each request.)
            raise ValueError(
                "batch='always' is not supported with shards > 1; use "
                "batch='auto' (sharded requests run serially through the mesh)"
            )
        images = [np.asarray(img) for img in images]
        if not images:
            raise ValueError("segment_stack: empty image stack")
        plans = [self.plan(img) for img in images]

        if batch == "always":
            use_batch = True
        elif batch == "never" or self.config.shards > 1:
            use_batch = False
        else:
            use_batch = _batch_pays(
                [p.problem.hoods.capacity for p in plans], jax.default_backend()
            )
        if not use_batch:
            results = [self.execute(p, seed=seed) for p in plans]
        else:
            joint = BucketKey(
                *(max(b[d] for b in (p.bucket for p in plans)) for d in range(3))
            )
            for p in plans:
                self.submit(p, seed=seed, bucket=joint)
            results = self.drain()
        mean_opt = float(np.mean([r.optimize_seconds for r in results]))
        return results, mean_opt


# ---------------------------------------------------------------------------
# module-level session registry (the deprecation shims' backing store)
# ---------------------------------------------------------------------------

_SESSIONS: "OrderedDict[ExecutionConfig, Segmenter]" = OrderedDict()

# Registry bound: each retained session can hold up to its configured
# max_cached_executables compiled programs, so an unbounded registry would
# leak under config sweeps (e.g. a beta scan through the legacy shims).
# LRU-evicted sessions just recompile on return — semantics unchanged.
MAX_SESSIONS = 8


def session_for(config: Optional[ExecutionConfig] = None) -> Segmenter:
    """Process-wide session per distinct config (LRU, ``MAX_SESSIONS``).

    One-shot callers (the deprecated ``segment_image`` path) repeatedly
    hitting the same config share a session — and therefore its executable
    cache — so even legacy traffic stops retracing.
    """
    config = config or ExecutionConfig()
    sess = _SESSIONS.get(config)
    if sess is None:
        sess = _SESSIONS[config] = Segmenter(config)
    else:
        _SESSIONS.move_to_end(config)
    while len(_SESSIONS) > MAX_SESSIONS:
        _SESSIONS.popitem(last=False)
    return sess


def default_session() -> Segmenter:
    return session_for(ExecutionConfig())


def reset_sessions() -> None:
    """Drop all module-level sessions (and their executable caches).

    Test hook: trace-count assertions need a cold cache."""
    _SESSIONS.clear()
