"""Continuous-batching segmentation serving engine (DESIGN.md §12, §14, §17).

The engine owns a fixed pool of ``max_batch`` slots over ONE
bucket-compiled ticked executable (``Segmenter.compile_ticked``).  EM for
every resident request advances in **ticks** — one ``run_em_ticked`` call
= up to ``tick_iters`` masked micro-steps per lane — instead of one
monolithic per-request ``while_loop``.  Between ticks the host retires
finished lanes (their ``done`` flag is the per-tick readback) and admits
pending requests into the freed slots in priority/deadline order, without
disturbing in-flight lanes and without ever retracing: the pool's shapes
are fixed at compile time, admission and retirement are pure data writes.

This is the slot-based continuous-batching scheduling model of production
LM servers (``repro.serving.lm``) applied to PMRF optimization: the
lockstep alternative (``run_em_batched``) runs every lane to the *slowest*
lane's convergence (the BENCH_api.json ``batched_speedup_x: 0.45``
inversion), while this engine keeps every slot busy with useful work —
a lane only ever pays its own iterations (plus at most one tick of
granularity waste, and not even that when the whole pool converges: the
ticked driver exits at the convergence boundary, DESIGN.md §17).

**Scheduling around latency (DESIGN.md §17).**  Tick size is the
throughput/latency dial: large ticks amortize the fixed per-tick cost
(host dispatch + device sync), small ticks return control to the host at
finer granularity so converged lanes retire and queued requests admit
sooner.  With ``tick_iters="auto"`` the engine *measures* its own
per-tick cost, fits the affine model ``cost(t) = a + b*t``, and picks the
ladder size minimizing expected cost per useful lane-micro-step —
shrinking ticks under light load or a near deadline, growing them at
saturation — with hysteresis so the executable-cache key (which includes
``tick_iters``) never thrashes.  Every ladder size is compiled once, up
front, through the session's LRU cache; switching sizes is a warm cache
hit, never a retrace.  ``stats()["tick_cost"]`` exposes the measured
breakdown so a regression in per-tick cost is visible, not silent.

Per-request results are bit-identical to serial ``run_em`` in every
label-visible output (labels, segmentation, mu, sigma, iteration counts)
regardless of tick-size schedule; energies agree to float-reduction
tolerance (DESIGN.md §12 — the same fusion-context caveat as
faithful-vs-static mode parity).

**Failure model (DESIGN.md §14).**  A poisoned request can never crash the
pool: requests are validated at ``submit`` (typed
:class:`~repro.api.errors.RequestError` / ``PlanError``); a lane that
diverges or degenerates on-device sets its traced ``status`` and freezes
exactly like a converged lane, so it retires through the ordinary path as
a :class:`SegCompletion` with an error ``status``; a lane that simply
never converges is evicted after a fixed micro-step residency budget.
Healthy co-resident lanes are bitwise unaffected (lanes are isolated in
every keyed reduction — chaos-tested).  Tick times feed a
:class:`~repro.training.fault.StragglerWatchdog`; execute failures retry
through the session's :class:`~repro.api.config.FallbackPolicy`.

Mixed-K traffic (DESIGN.md §13): the pool is compiled at the session's
``n_labels``; requests with fewer labels are admitted by label-padding
their lanes with inert sentinel labels (bitwise natural-K trajectories),
requests with more labels are rejected at ``submit``.
"""

from __future__ import annotations

import heapq
import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro import obs
from repro.analysis import budget as budget_mod
from repro.api.config import ExecutionConfig
from repro.api.errors import FallbackError, RequestError
from repro.api.session import BucketKey, Plan, Segmenter
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import pipeline as pipeline_mod
from repro.planning import costmodel as planning_mod
from repro.planning.lsq import DecayedAffineFit
from repro.testing import chaos as chaos_mod
from repro.training.fault import StragglerWatchdog

_INF = math.inf

#: Completion statuses that mean "the result is a legitimate segmentation".
OK_COMPLETION_STATUSES = ("converged", "max_iters")

#: Default adaptive tick-size ladder.  Powers of two so the policy's
#: argmin scans a handful of sizes; 32+ is never optimal on measured CPU
#: cost curves (fixed cost a ~= 2-10ms, marginal b ~= 5ms/step, typical
#: request length S ~= 65 micro-steps puts the optimum near sqrt(2aS/b)).
DEFAULT_TICK_LADDER = (1, 2, 4, 8, 16)

# ---------------------------------------------------------------------------
# Pool surgery ops — module level so their jit caches are shared by every
# engine instance (keyed on pool shapes).  When these lived as per-engine
# ``jax.jit(lambda ...)`` closures, every fresh engine — including each
# fault-sweep engine in bench_serve — paid ~0.5s recompiling identical
# programs mid-serving (DESIGN.md §17's regression post-mortem).
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0,))
def _write_pools(pools, lanes, slot):
    """One fused dispatch per lane write instead of ~30 eager per-leaf ops
    (measured ~75ms/admission eager vs ~1ms jitted).  ``slot`` is a traced
    scalar, so every slot shares one trace; donating the pools makes the
    writes in-place where XLA allows."""
    return jax.tree.map(lambda p, o: p.at[slot].set(o), pools, lanes)


@jax.jit
def _read_lane(state, slot):
    return jax.tree.map(lambda x: x[slot], state)


@partial(jax.jit, donate_argnums=(0,))
def _mark_done(state, slot):
    """Slot-local eviction write: the lane freezes and frees up for the
    next admission; other lanes' leaves pass through untouched."""
    return state._replace(done=state.done.at[slot].set(True))


@partial(jax.jit, donate_argnums=(0,))
def _hold_lane_op(state, slot, dmu):
    """Chaos never-converge hold: reset one lane's progress + nudge its mu
    (slot-local; co-resident lanes stay bitwise untouched)."""
    return state._replace(
        mu=state.mu.at[slot].add(dmu),
        map_hist=state.map_hist.at[slot].set(0.0),
        map_i=state.map_i.at[slot].set(0),
        map_done=state.map_done.at[slot].set(False),
        total_hist=state.total_hist.at[slot].set(0.0),
        em_i=state.em_i.at[slot].set(0),
        done=state.done.at[slot].set(False),
        status=state.status.at[slot].set(em_mod.STATUS_OK),
    )


@dataclass
class SegRequest:
    """One queued segmentation request.

    Admission order is ``(priority, deadline, rid)``: lower ``priority``
    values are served strictly first (0 is the default class; negative for
    latency-sensitive traffic, positive for batch/background), then
    earliest ``deadline_s`` (``None`` sorts last), then lowest ``rid`` —
    a total, deterministic order even when every deadline is ``None``.
    ``deadline_s`` is a *scheduling priority*, not an enforced SLO — the
    engine reports per-request latency so callers can check deadlines
    themselves — but an adaptive engine also shrinks its tick size when
    the nearest live deadline gets close (DESIGN.md §17).
    """

    rid: int
    plan: Plan
    seed: int = 0
    deadline_s: Optional[float] = None
    priority: int = 0
    submitted_s: float = field(default_factory=time.perf_counter)


@dataclass
class SegCompletion:
    """A finished request with its result, health, and latency accounting.

    Latency is reported in two honest, disjoint parts (DESIGN.md §17):
    ``queue_s`` (submit -> admit: time waiting for a slot, a function of
    load) and ``residence_s`` (admit -> retire: time resident in a lane, a
    function of tick granularity and per-step cost).  ``latency_s`` is
    their sum — what the client experiences.  Conflating the two was how
    the 0.67x regression hid: queue wait under a batch-dump arrival
    pattern dominated p50 and made per-tick cost invisible.

    ``status`` is the engine's disposition of the request: the lane's
    device-reported health (``"converged"`` / ``"max_iters"`` /
    ``"diverged"`` / ``"degenerate"``, see ``em.STATUS_NAMES``) for a
    naturally retired lane, or ``"evicted"`` for a lane the engine force-
    retired (per-lane residency budget or the global ``run()`` cap).
    ``result`` is always present — for an error completion it holds the
    lane's last state (labels are always finite ints; parameters may be
    non-finite for a diverged lane).
    """

    rid: int
    result: pipeline_mod.SegmentationResult
    latency_s: float        # submit -> retire (what the client experiences)
    queue_s: float          # submit -> admit (time spent waiting for a slot)
    residence_s: float      # admit -> retire (time resident in a lane)
    ticks_resident: int
    slot: int
    status: str = "converged"

    @property
    def service_s(self) -> float:
        """Deprecated alias for :attr:`residence_s` (pre-§17 name)."""
        return self.residence_s

    @property
    def ok(self) -> bool:
        return self.status in OK_COMPLETION_STATUSES


class SegmentationEngine:
    """Fixed-slot continuous-batching server for segmentation requests.

    Lifecycle::

        sess = api.Segmenter(api.ExecutionConfig())
        eng = SegmentationEngine(sess, max_batch=8, tick_iters="auto")
        for rid, img in enumerate(images):
            eng.submit(img, rid=rid)
        completions = eng.run()

    The pool bucket is fixed on first use: pass ``bucket=`` explicitly or
    let the engine take the elementwise max over the requests pending at
    first tick.  Later submissions must fit that bucket (padding up is
    fine; exceeding it raises — recompile a new engine for bigger work).

    ``tick_iters`` is either a fixed int or ``"auto"`` (adaptive: the
    engine picks from ``tick_ladder`` using its measured per-tick cost
    model, see the module docstring; ``tick_hysteresis`` consecutive
    agreeing choices are required before a switch, and every ladder size
    is compiled up front so switches never stall serving).

    ``max_ticks_resident`` bounds how long any single lane may occupy a
    slot, expressed in ticks of the *initial* tick size (default: the
    ticks a worst-case ``max_em_iters x max_map_iters`` run needs, plus
    slack); internally it is enforced as a micro-step budget so adaptive
    resizing and early tick exits can't distort it.  A lane exceeding it
    is force-retired as an ``"evicted"`` error completion, so one
    pathological request can never starve the pool.  Thread-unsafe by
    design, like the :class:`Segmenter` it drives.
    """

    def __init__(
        self,
        session: Union[Segmenter, ExecutionConfig, None] = None,
        *,
        max_batch: int = 8,
        tick_iters: Union[int, str] = 8,
        tick_ladder: Optional[Sequence[int]] = None,
        tick_hysteresis: int = 2,
        deadline_margin: float = 2.0,
        bucket: Optional[BucketKey] = None,
        max_ticks_resident: Optional[int] = None,
        watchdog: Optional[StragglerWatchdog] = None,
    ):
        if session is None:
            session = Segmenter(ExecutionConfig())
        elif isinstance(session, ExecutionConfig):
            session = Segmenter(session)
        if session.config.shards > 1:
            raise ValueError(
                "SegmentationEngine is single-device (the slot axis is the "
                "parallel axis); use a shards=1 session"
            )
        self.adaptive = tick_iters == "auto"
        if self.adaptive:
            ladder = tuple(sorted(set(tick_ladder or DEFAULT_TICK_LADDER)))
            if not ladder or any(t < 1 for t in ladder):
                raise ValueError(f"tick_ladder entries must be >= 1, got {ladder}")
            tick_iters = ladder[min(len(ladder) - 1, len(ladder) // 2)]
        else:
            if not isinstance(tick_iters, int):
                raise ValueError(
                    f"tick_iters must be an int or 'auto', got {tick_iters!r}"
                )
            ladder = (tick_iters,)
        if max_batch < 1 or tick_iters < 1:
            raise ValueError("max_batch and tick_iters must be >= 1")
        if tick_hysteresis < 1:
            raise ValueError("tick_hysteresis must be >= 1")
        self.session = session
        self.max_batch = max_batch
        self.tick_iters = tick_iters          # CURRENT tick size
        self.tick_ladder = ladder
        self.tick_hysteresis = tick_hysteresis
        self.deadline_margin = float(deadline_margin)
        self.bucket: Optional[BucketKey] = (
            BucketKey(*bucket) if bucket is not None else None
        )
        if max_ticks_resident is None:
            # Worst-case resident work for a healthy lane: every micro-step
            # advances the MAP loop, so a full run is at most
            # max_em_iters * max_map_iters micro-steps; +2 ticks of slack
            # for boundary granularity.  Anything beyond this is a lane
            # that cannot make progress.
            cfg = session.config
            max_ticks_resident = (
                -(-cfg.max_em_iters * cfg.max_map_iters // tick_iters) + 2
            )
        if max_ticks_resident < 1:
            raise ValueError("max_ticks_resident must be >= 1")
        self.max_ticks_resident = max_ticks_resident
        self._max_steps_resident = max_ticks_resident * tick_iters
        self.watchdog = watchdog if watchdog is not None else StragglerWatchdog()

        self._heap: List[tuple] = []   # (priority, deadline key, rid, seq, req)
        self._seq = 0
        self._auto_rid = 0
        self._live_rids: set = set()   # queued + resident (dropped on retire)
        self._exe = None
        self._hoods = self._model = self._state = None
        self.slot_req: List[Optional[SegRequest]] = [None] * max_batch
        self._slot_admit_s = np.zeros(max_batch, np.float64)
        self._slot_admit_tick = np.zeros(max_batch, np.int64)
        self._slot_admit_steps = np.zeros(max_batch, np.int64)
        self._slot_hold = [False] * max_batch   # chaos: never-converge lanes
        self.completions: List[SegCompletion] = []
        self.ticks = 0
        self.admitted = 0
        self.evicted = 0
        self.error_completions = 0
        self.total_steps = 0           # micro-steps actually issued per slot
        self.lane_steps = 0            # occupied-lane micro-steps issued
        self.steps_saved = 0           # tick_iters - steps (early tick exits)
        self.tick_switches: List[Tuple[int, int, int]] = []  # (tick, from, to)
        self.fallback_events: List[Dict] = []
        # Per-tick cost instrumentation (DESIGN.md §17): host-phase timers,
        # each read off its span (serve.admit/advance/sync/retire, repro.obs),
        # plus a decayed least-squares fit of cost(t) = a + b*t over
        # (steps_executed, tick_duration) observations.
        self._phase_s = {"admit": 0.0, "advance": 0.0, "sync": 0.0, "retire": 0.0}
        self._size_ticks: Dict[int, int] = {}
        self._size_s: Dict[int, float] = {}
        # One cost-model implementation, two consumers (DESIGN.md §18):
        # the online tick-cost fit is the same DecayedAffineFit the
        # calibration machinery uses, and until it has observations it
        # falls back to the calibrated table's tick-cost prior instead of
        # blind constants (see _tick_cost_prior).
        self._cm = DecayedAffineFit(decay=0.95)
        self._tick_prior: Optional[Tuple[float, float]] = None
        self._steps_ewma: Optional[float] = None   # micro-steps per request
        self._desired_streak: Tuple[int, int] = (tick_iters, 0)

    # ------------------------------------------------------------------
    # submission (priority/deadline-ordered queue)
    # ------------------------------------------------------------------

    def _validate_plan(self, plan: Plan) -> None:
        """Admission validation (DESIGN.md §14): a request that would
        poison its lane is rejected here, before it costs a slot.  Images
        were already validated by ``Segmenter.plan``; this guards prepared
        :class:`Plan` objects (and post-plan corruption)."""
        model = plan.problem.model
        for name in ("region_mean", "region_weight"):
            arr = np.asarray(getattr(model, name))
            if not np.isfinite(arr).all():
                bad = int(arr.size - np.isfinite(arr).sum())
                raise RequestError(
                    f"plan model {name} contains {bad} non-finite value(s); "
                    "the lane's first energy evaluation would diverge"
                )
        if not (
            np.isfinite(float(model.beta)) and np.isfinite(float(model.sigma_min))
        ):
            raise RequestError("plan model beta/sigma_min must be finite")

    def submit(
        self,
        image_or_plan,
        *,
        rid: Optional[int] = None,
        seed: int = 0,
        deadline_s: Optional[float] = None,
        priority: int = 0,
    ) -> int:
        """Enqueue a request (image or prepared :class:`Plan`); returns its
        rid.  ``deadline_s`` is seconds from now.  Admission order is
        ``(priority, deadline, rid)`` — deterministic even when every
        deadline is ``None`` (equal keys tie-break by request id, so
        auto-assigned rids degrade to FIFO).  Invalid requests raise typed
        errors (``PlanError`` for unusable images, :class:`RequestError`
        for plans failing admission validation) and never enter the queue.
        """
        plan = (
            image_or_plan
            if isinstance(image_or_plan, Plan)
            else self.session.plan(image_or_plan)
        )
        self._validate_plan(plan)
        if deadline_s is not None and not math.isfinite(deadline_s):
            raise RequestError(f"deadline_s must be finite, got {deadline_s!r}")
        if self.bucket is not None and not _fits(plan.bucket, self.bucket):
            raise RequestError(
                f"request bucket {tuple(plan.bucket)} exceeds the engine's "
                f"fixed pool bucket {tuple(self.bucket)}"
            )
        plan_labels = plan.problem.model.n_labels
        if plan_labels > self.session.config.n_labels:
            raise RequestError(
                f"request has {plan_labels} labels but the pool serves "
                f"n_labels={self.session.config.n_labels}; smaller-K "
                "requests are label-padded with inert labels, larger-K "
                "need a wider pool (DESIGN.md §13)"
            )
        if rid is None:
            while self._auto_rid in self._live_rids:
                self._auto_rid += 1
            rid = self._auto_rid
            self._auto_rid += 1
        elif not isinstance(rid, (int, np.integer)):
            raise RequestError(
                f"rid must be an int (it tie-breaks the admission heap), "
                f"got {type(rid).__name__}"
            )
        elif rid in self._live_rids:
            raise RequestError(
                f"rid {rid} is already queued or in flight; completions are "
                "keyed by rid, so live rids must be unique"
            )
        self._live_rids.add(rid)
        req = SegRequest(
            rid=rid,
            plan=plan,
            seed=seed,
            deadline_s=(
                None if deadline_s is None else time.perf_counter() + deadline_s
            ),
            priority=int(priority),
        )
        key = _INF if req.deadline_s is None else req.deadline_s
        heapq.heappush(self._heap, (req.priority, key, int(rid), self._seq, req))
        self._seq += 1
        return rid

    def pending(self) -> int:
        return len(self._heap)

    def active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    # ------------------------------------------------------------------
    # pool bring-up, admission, retirement
    # ------------------------------------------------------------------

    def _ensure_pool(self) -> None:
        if self._exe is not None:
            return
        if self.bucket is None:
            if not self._heap:
                raise RuntimeError("cannot size the pool: no bucket, no pending")
            self.bucket = BucketKey(
                *(
                    max(item[-1].plan.bucket[d] for item in self._heap)
                    for d in range(3)
                )
            )
        # Adaptive engines compile the whole ladder up front (through the
        # session's LRU, so a sibling engine on the same session pays
        # nothing): a tick-size switch must be a warm cache hit, never a
        # mid-serving compile stall.
        for size in self.tick_ladder:
            exe = self.session.compile_ticked(
                self.bucket, batch=self.max_batch, tick_iters=size
            )
            if size == self.tick_iters:
                self._exe = exe
        self._hoods, self._model, self._state = self.session.ticked_pool(
            self.bucket, batch=self.max_batch
        )

    def _admit(self) -> int:
        """Fill free slots from the queue in priority/deadline order.  Pure
        data writes into the pool (per-slot ``.at[slot].set``) — in-flight
        lanes are untouched and the compiled tick program never retraces."""
        admitted = 0
        now = time.perf_counter()
        for slot in range(self.max_batch):
            if not self._heap or self.slot_req[slot] is not None:
                continue
            req = heapq.heappop(self._heap)[-1]
            # Memoized admission (§17): the lane's initial TickState is a
            # pure function of the plan's padded inputs, so repeat traffic
            # pays zero host-side init work.
            h1, m1, lane = self.session.lane_state(
                req.plan, bucket=self.bucket, seed=req.seed
            )
            hold = False
            if chaos_mod.is_active():
                # Post-validation corruption hooks (DESIGN.md §14): the
                # chaos harness returns fresh arrays, never mutates the
                # plan's memoized inputs — so an identity check tells us
                # whether this admission was corrupted and must rebuild
                # its lane state from the corrupted arrays.
                _, _, lab0, mu0, sig0 = self.session.lane_inputs(
                    req.plan, bucket=self.bucket, seed=req.seed
                )
                m1c, lab0c, mu0c, sig0c = chaos_mod.on_admit(
                    req.rid, m1, lab0, mu0, sig0
                )
                if not (
                    m1c is m1 and lab0c is lab0
                    and mu0c is mu0 and sig0c is sig0
                ):
                    m1 = m1c
                    lane = em_mod.init_tick_lane(
                        lab0c, mu0c, sig0c, self.bucket.n_hoods
                    )
                hold = chaos_mod.hold_lane(req.rid)
            self._hoods, self._model, self._state = _write_pools(
                (self._hoods, self._model, self._state), (h1, m1, lane), slot
            )
            self.slot_req[slot] = req
            self._slot_admit_s[slot] = now
            self._slot_admit_tick[slot] = self.ticks
            self._slot_admit_steps[slot] = self.total_steps
            self._slot_hold[slot] = hold
            self.admitted += 1
            admitted += 1
        return admitted

    def _complete_slot(self, slot: int, status: Optional[str] = None) -> None:
        """Assemble a completion from a slot's current lane state and free
        the slot.  ``status=None`` takes the lane's device-reported health
        (natural retirement); an explicit string marks an engine-side
        disposition (``"evicted"``)."""
        req = self.slot_req[slot]
        now = time.perf_counter()
        res = em_mod.tick_result(_read_lane(self._state, slot))
        residence_s = now - self._slot_admit_s[slot]
        result = pipeline_mod._assemble_result(
            req.plan.problem, res, req.plan.init_seconds, residence_s
        )
        completion_status = result.status if status is None else status
        if completion_status not in OK_COMPLETION_STATUSES:
            self.error_completions += 1
        else:
            # Request-length estimate for the adaptive tick policy: EWMA of
            # micro-steps (total MAP iterations) per healthy completion.
            steps = float(result.map_iters)
            self._steps_ewma = (
                steps
                if self._steps_ewma is None
                else 0.7 * self._steps_ewma + 0.3 * steps
            )
        self.completions.append(
            SegCompletion(
                rid=req.rid,
                result=result,
                latency_s=now - req.submitted_s,
                queue_s=self._slot_admit_s[slot] - req.submitted_s,
                residence_s=residence_s,
                ticks_resident=int(self.ticks - self._slot_admit_tick[slot]),
                slot=slot,
                status=completion_status,
            )
        )
        self.slot_req[slot] = None
        self._slot_hold[slot] = False
        self._live_rids.discard(req.rid)

    def _retire(self, done: Optional[np.ndarray] = None) -> int:
        """Drain finished lanes — converged AND quarantined: a diverged or
        degenerate lane set ``done`` device-side and froze, so sick lanes
        leave through this exact path as error-status completions.  The
        per-tick device->host sync is the (max_batch,) ``done`` vector;
        full lane state is fetched only for lanes actually retiring."""
        if done is None:
            done = np.asarray(self._state.done)
        retired = 0
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None or not done[slot]:
                continue
            self._complete_slot(slot)
            retired += 1
        return retired

    def _evict_overstayers(self) -> int:
        """Force-retire lanes whose issued micro-steps exceed the residency
        budget as ``"evicted"`` error completions (DESIGN.md §14).  The
        lane's pool slot is marked ``done`` device-side (a slot-local
        write), so it freezes and frees up for the next admission."""
        evicted = 0
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None:
                continue
            resident = self.total_steps - self._slot_admit_steps[slot]
            if resident < self._max_steps_resident:
                continue
            self._state = _mark_done(self._state, slot)
            self._complete_slot(slot, status="evicted")
            self.evicted += 1
            evicted += 1
        return evicted

    # ------------------------------------------------------------------
    # adaptive tick-size policy (DESIGN.md §17)
    # ------------------------------------------------------------------

    def _record_tick(self, steps: int, duration: float) -> None:
        """Feed one tick's (steps issued, wall duration) into the decayed
        least-squares cost model and the per-size ledgers."""
        size = self.tick_iters
        self._size_ticks[size] = self._size_ticks.get(size, 0) + 1
        self._size_s[size] = self._size_s.get(size, 0.0) + duration
        self._cm.observe(steps, duration)

    def _tick_cost_default(self) -> Tuple[float, float]:
        """Cold-start ``(a, b)`` for the tick-cost fit: the calibrated
        plan model's prediction for this pool (DESIGN.md §18) — per-launch
        dispatch as ``a``, one pool micro-step as ``b`` — so the first
        adaptive decisions start from measured-platform numbers instead of
        blind constants.  Falls back to the historical ``(5e-3, 5e-3)``
        when the bucket is still unknown or autotuning is disabled."""
        if self._tick_prior is not None:
            return self._tick_prior
        if self.bucket is None or planning_mod.autotune_disabled():
            return 5e-3, 5e-3
        cfg = self.session.config
        self._tick_prior = planning_mod.model_for(cfg).tick_cost_prior(
            mode=cfg.mode,
            bucket=self.bucket,
            width=self.max_batch,
            n_labels=cfg.n_labels,
            precision=cfg.precision,
        )
        return self._tick_prior

    def cost_model(self) -> Tuple[float, float]:
        """Fitted per-tick cost ``(a, b)``: ``cost ~= a + b*steps`` seconds
        (fixed host+dispatch overhead vs marginal micro-step cost).

        The intercept is floored at the *measured* per-tick host overhead
        (the admit/advance/retire phase timers — bookkeeping every tick
        pays regardless of size).  Without the floor, noise in a run of
        small-tick observations can drive the fitted ``a`` to zero, and a
        zero fixed cost makes the utility ``b / eff(t)`` monotone in
        favor of the smallest ladder size — a permanent small-tick
        lock-in that costs ~15-20% throughput under saturation."""
        ph = self._phase_s
        a_floor = (
            (ph["admit"] + ph["advance"] + ph["retire"]) / self.ticks
            if self.ticks
            else 0.0
        )
        return self._cm.fit(a_floor=a_floor, default=self._tick_cost_default())

    def _request_steps_estimate(self) -> float:
        if self._steps_ewma is not None:
            return max(self._steps_ewma, 1.0)
        cfg = self.session.config
        return max(cfg.max_em_iters * cfg.max_map_iters / 4.0, 1.0)

    def _nearest_deadline_slack(self) -> Optional[float]:
        """Seconds until the tightest live deadline (resident or queued);
        None when nothing carries a deadline."""
        nearest = None
        for req in self.slot_req:
            if req is not None and req.deadline_s is not None:
                nearest = req.deadline_s if nearest is None else min(nearest, req.deadline_s)
        for item in self._heap:
            dl = item[-1].deadline_s
            if dl is not None:
                nearest = dl if nearest is None else min(nearest, dl)
        if nearest is None:
            return None
        return nearest - time.perf_counter()

    def _desired_tick_iters(self) -> int:
        """Ladder size minimizing expected cost per *useful* micro-step.

        A request of S micro-steps served in ticks of t wastes on average
        ~t/2 trailing steps (granularity) — well approximated by an
        efficiency factor (1 - t/2S) — while each tick pays the fixed cost
        ``a`` once.  Minimizing ``(a + b*t) / (t * (1 - t/2S))`` trades
        amortization against granularity waste; an empty queue or an
        urgent class present halves the effective S (turnaround matters
        more than amortization), and a near deadline clamps t down so one
        tick can't blow through it.
        """
        a, b = self.cost_model()
        s_est = self._request_steps_estimate()
        urgent = any(
            req is not None and req.priority < 0 for req in self.slot_req
        ) or any(item[0] < 0 for item in self._heap)
        if not self._heap or urgent:
            s_est = max(s_est / 2.0, 2.0)
        best, best_u = self.tick_ladder[0], _INF
        for t in self.tick_ladder:
            eff = max(1.0 - t / (2.0 * s_est), 0.25)
            u = (a + b * t) / (t * eff)
            if u < best_u - 1e-12:
                best, best_u = t, u
        slack = self._nearest_deadline_slack()
        if slack is not None:
            below = [t for t in self.tick_ladder if t <= best]
            while len(below) > 1 and (a + b * below[-1]) * self.deadline_margin > max(
                slack, 0.0
            ):
                below.pop()
            best = below[-1]
        return best

    def _maybe_resize_tick(self) -> None:
        """Apply the adaptive policy with hysteresis: only switch after
        ``tick_hysteresis`` consecutive ticks agree on the same new size
        (the executable-cache key includes tick_iters — thrashing sizes
        would thrash the warm-path guarantee tests pin)."""
        if not self.adaptive:
            return
        desired = self._desired_tick_iters()
        if desired == self.tick_iters:
            self._desired_streak = (desired, 0)
            return
        size, streak = self._desired_streak
        streak = streak + 1 if size == desired else 1
        self._desired_streak = (desired, streak)
        if streak < self.tick_hysteresis:
            return
        self.tick_switches.append((self.ticks, self.tick_iters, desired))
        self.tick_iters = desired
        self._desired_streak = (desired, 0)
        # Warm LRU hit: the whole ladder was compiled at pool bring-up.
        self._exe = self.session.compile_ticked(
            self.bucket, batch=self.max_batch, tick_iters=desired
        )

    # ------------------------------------------------------------------
    # the tick
    # ------------------------------------------------------------------

    def _try_tick(self):
        chaos_mod.on_execute(self._exe.key.backend)
        return self._exe(self._hoods, self._model, self._state)

    def _advance_pool(self):
        """One ticked-executable call under the session's fallback policy
        (DESIGN.md §14): execute failures retry same-backend with capped
        exponential backoff, then recompile the pool program on the
        fallback backend and replay the tick.  Pool state is untouched by
        a failed call (the ticked program donates nothing), so the replay
        is exact.  Returns ``(state, steps_executed)``."""
        policy = self.session.config.fallback
        delay = policy.backoff_s
        err = None
        for attempt in range(policy.max_retries + 1):
            try:
                return self._try_tick()
            except Exception as e:   # noqa: BLE001 — fault boundary
                err = e
                if attempt < policy.max_retries:
                    time.sleep(min(delay, policy.max_backoff_s))
                    delay *= 2
        if not (policy.enabled and self._exe.key.backend != policy.backend):
            raise err
        self.fallback_events.append(
            {
                "stage": "tick",
                "from": self._exe.key.backend,
                "to": policy.backend,
                "error": repr(err),
            }
        )
        self._exe = self.session.compile_ticked(
            self.bucket,
            batch=self.max_batch,
            tick_iters=self.tick_iters,
            backend=policy.backend,
        )
        try:
            return self._try_tick()
        except Exception as fb_e:   # noqa: BLE001
            raise FallbackError(
                f"tick failed on {self.fallback_events[-1]['from']!r} and on "
                f"fallback backend {policy.backend!r}"
            ) from fb_e

    def step(self) -> int:
        """One engine tick: admit, advance every live lane by up to
        ``tick_iters`` micro-steps (the driver exits early once the whole
        pool is done), retire finished/quarantined lanes, evict
        overstayers, then let the adaptive policy reconsider the tick
        size.  Returns the number of lanes advanced (0 = nothing to do)."""
        with obs.span("serve.admit") as admit:
            if self._heap:
                self._ensure_pool()
                self._admit()
            n_active = self.active()
        if n_active == 0:
            return 0
        self._phase_s["admit"] += admit.seconds
        with obs.span("serve.advance") as advance:
            chaos_mod.on_tick(self.ticks)
            self._state, steps_dev = self._advance_pool()
        self._phase_s["advance"] += advance.seconds
        # THE per-tick sync point: one host fetch for the done vector and
        # the executed-step count together.
        with obs.span("serve.sync") as sync:
            done, steps = jax.device_get((self._state.done, steps_dev))
            done = np.array(done)   # writable copy: chaos holds flip entries
            steps = int(steps)
        self._phase_s["sync"] += sync.seconds
        tick_s = sync.end - advance.start
        self.watchdog.observe(self.ticks, tick_s)
        self._record_tick(steps, tick_s)
        self.ticks += 1
        self.total_steps += steps
        self.lane_steps += n_active * steps
        self.steps_saved += self.tick_iters - steps
        # Mirror into the analysis ledger (DESIGN.md §15) so the budget
        # sentinel sees serving activity alongside trace/compile events.
        budget_mod.LEDGER.bump("serve", "ticks")
        budget_mod.LEDGER.bump("serve", "lane_steps", n_active * steps)
        with obs.span("serve.retire") as retire:
            # Chaos never-converge holds: reset held lanes' progress before
            # retirement so they can only leave via eviction.  Slot-local
            # writes — co-resident lanes stay bitwise untouched.
            for slot in range(self.max_batch):
                if self._slot_hold[slot] and self.slot_req[slot] is not None:
                    req = self.slot_req[slot]
                    dmu = chaos_mod.monkey().hold_perturbation(
                        req.rid, self.ticks, int(np.asarray(self._state.mu).shape[1])
                    )
                    self._state = _hold_lane_op(self._state, slot, dmu)
                    done[slot] = False
            self._retire(done)
            self._evict_overstayers()
        self._phase_s["retire"] += retire.seconds
        self._maybe_resize_tick()
        return n_active

    def run(self, max_ticks: int = 1_000_000) -> List[SegCompletion]:
        """Drive until queue and pool are empty; returns (and clears) the
        completions, in retirement order.

        Hitting ``max_ticks`` no longer raises (DESIGN.md §14): finished
        lanes have already retired through :meth:`step`, and remaining
        residents are force-retired as ``"evicted"`` error completions —
        partial results and all latency accounting are preserved.  (With
        per-lane residency eviction, the global cap is only reachable
        through sustained oversubscription.)  Still-queued requests stay
        queued; ``run()`` again continues them.
        """
        while self._heap or self.active():
            if self.ticks >= max_ticks:
                for slot in range(self.max_batch):
                    if self.slot_req[slot] is not None:
                        self._state = _mark_done(self._state, slot)
                        self._complete_slot(slot, status="evicted")
                        self.evicted += 1
                break
            self.step()
        done, self.completions = self.completions, []
        return done

    def stats(self) -> dict:
        """Occupancy/throughput/health counters plus the per-tick cost
        breakdown (DESIGN.md §17) for benchmarks and smoke checks."""
        cap = max(self.total_steps * self.max_batch, 1)
        a, b = self.cost_model()
        per_size = {
            size: {
                "ticks": n,
                "mean_s": round(self._size_s[size] / n, 6),
            }
            for size, n in sorted(self._size_ticks.items())
        }
        return {
            "ticks": self.ticks,
            "tick_iters": self.tick_iters,
            "adaptive": self.adaptive,
            "tick_ladder": list(self.tick_ladder),
            "tick_switches": len(self.tick_switches),
            "max_batch": self.max_batch,
            "admitted": self.admitted,
            "total_steps": self.total_steps,
            "lane_steps": self.lane_steps,
            "steps_saved_early_exit": self.steps_saved,
            "occupancy": round(self.lane_steps / cap, 4),
            "evicted": self.evicted,
            "error_completions": self.error_completions,
            "straggler_events": len(self.watchdog.events),
            "fallbacks": len(self.fallback_events),
            "tick_cost": {
                "phase_s": {k: round(v, 6) for k, v in self._phase_s.items()},
                "per_size": per_size,
                "model_fixed_s": round(a, 6),
                "model_per_step_s": round(b, 6),
                "request_steps_est": round(self._request_steps_estimate(), 2),
            },
        }


def _fits(inner: BucketKey, outer: BucketKey) -> bool:
    return all(i <= o for i, o in zip(inner, outer))
