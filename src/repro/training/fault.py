"""Fault-tolerance machinery: straggler watchdog, preemption handling,
and the restartable trainer loop used by ``launch/train.py``.

The failure model for a 1000+-node fleet:

* **node loss / preemption** — the job dies (or receives SIGTERM with a
  grace window).  Recovery = restart from the last committed checkpoint,
  possibly on a *different* mesh (elastic re-mesh restore, checkpoint.py).
  ``run_training`` is written so that killing the process at any point and
  re-invoking it resumes exactly (stateless data addressing + atomic
  commits); tests/test_training.py injects a crash mid-save and verifies.
* **stragglers** — a slow host stretches every step (SPMD is bulk-
  synchronous).  The watchdog tracks a step-time EWMA; a step exceeding
  ``threshold x EWMA`` raises a report so the orchestrator can
  checkpoint-and-reschedule away from the slow node.  (On-fleet the signal
  feeds the cluster scheduler; here it is logged and surfaced.)
* **preemption signal** — SIGTERM triggers a final synchronous save at
  the next step boundary before exit (the standard TPU maintenance-event
  protocol).
"""

from __future__ import annotations

import signal
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.serving.watchdog import StragglerWatchdog


class PreemptionHandler:
    """SIGTERM -> graceful-save flag, checked at step boundaries."""

    def __init__(self, install: bool = True):
        self._requested = False
        self._prev = None
        if install:
            try:
                self._prev = signal.signal(signal.SIGTERM, self._on_signal)
            except ValueError:  # non-main thread (tests)
                self._prev = None

    def _on_signal(self, signum, frame):
        self._requested = True

    def request(self) -> None:  # test hook / manual trigger
        self._requested = True

    @property
    def requested(self) -> bool:
        return self._requested

    def restore(self) -> None:
        if self._prev is not None:
            signal.signal(signal.SIGTERM, self._prev)


@dataclass
class TrainLoopReport:
    last_step: int
    losses: List[float]
    straggler_events: List[Dict[str, float]]
    preempted: bool
    resumed_from: Optional[int]


def run_training(
    *,
    step_fn: Callable[[Any, Dict[str, Any]], Any],
    state: Any,
    make_batch: Callable[[int], Dict[str, Any]],
    num_steps: int,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    state_specs: Any = None,
    mesh: Any = None,
    keep_last: int = 3,
    log_every: int = 10,
    log_fn: Callable[[str], None] = print,
    watchdog: Optional[StragglerWatchdog] = None,
    preemption: Optional[PreemptionHandler] = None,
    crash_at_step: Optional[int] = None,   # failure-injection test hook
) -> TrainLoopReport:
    """Restartable training loop.

    Resumes from the latest committed checkpoint in ``ckpt_dir`` when one
    exists; saves every ``ckpt_every`` steps (async) and at preemption
    (sync).  ``crash_at_step`` raises mid-loop *after* the step executes
    but before its checkpoint commits — the recovery test uses this to
    prove restart-exactness.
    """
    from repro.training import checkpoint as CK

    watchdog = watchdog or StragglerWatchdog()
    preemption = preemption or PreemptionHandler(install=False)
    ckpt = CK.AsyncCheckpointer(ckpt_dir, keep_last=keep_last) if ckpt_dir else None

    start_step = 0
    resumed_from = None
    if ckpt_dir:
        latest = CK.latest_step(ckpt_dir)
        if latest is not None:
            start_step, state, _ = CK.restore_checkpoint(
                ckpt_dir, state, mesh=mesh
            )
            resumed_from = start_step
            log_fn(f"[fault] resumed from committed step {start_step}")

    losses: List[float] = []
    preempted = False
    step = start_step
    while step < num_steps:
        batch = make_batch(step)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0

        loss = float(metrics["loss"])
        losses.append(loss)
        if watchdog.observe(step, dt):
            log_fn(
                f"[fault] straggler: step {step} took {dt:.3f}s "
                f"(ewma {watchdog.ewma:.3f}s)"
            )
        if log_every and step % log_every == 0:
            log_fn(f"step {step:5d}  loss {loss:.4f}  ({dt*1e3:.1f} ms)")

        step += 1

        if crash_at_step is not None and step == crash_at_step:
            raise RuntimeError(f"injected failure at step {step}")

        if ckpt and step % ckpt_every == 0:
            ckpt.save(step, state, specs=state_specs, mesh=mesh)

        if preemption.requested:
            log_fn(f"[fault] preemption requested: sync save at step {step}")
            if ckpt:
                ckpt.wait()
                CK.save_checkpoint(
                    ckpt_dir, step, state, specs=state_specs, mesh=mesh,
                    keep_last=keep_last,
                )
            preempted = True
            break

    if ckpt:
        ckpt.wait()
        if not preempted and (step % ckpt_every != 0 or step == start_step):
            CK.save_checkpoint(
                ckpt_dir, step, state, specs=state_specs, mesh=mesh,
                keep_last=keep_last,
            )

    return TrainLoopReport(
        last_step=step,
        losses=losses,
        straggler_events=watchdog.events,
        preempted=preempted,
        resumed_from=resumed_from,
    )
