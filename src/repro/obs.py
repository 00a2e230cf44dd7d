"""Spans inside the program, and the XLA compiles that run in them.

``span(name, req=...)`` times one stage of the program on the host thread.
It always opens a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``,
so under the JAX profiler the stage sits on the profiler's clock, in the
same ``.xplane.pb`` as the device operations it launched.  The span it
yields has ``seconds``, which the program reads for its own timing fields
(``Plan.init_seconds``, ``SegmentationResult.optimize_seconds``,
``Executable.compile_seconds``, the serving engine's phase timers).

While a recording is open, each span is also kept in memory as a
:class:`Record`: its name, start and end (``time.perf_counter``), the
innermost recorded span it ran in, its request id (a plan id; a child
span takes its parent's), and the XLA compiles
(``/jax/core/compile/backend_compile_duration``) that ran in it and in no
recorded child.  A recording is open

* inside ``with recording() as rec:``, whose spans go to ``rec``;
* while the JAX profiler traces: those spans go to the recorder that
  :func:`traced` returns, from the first span the profiler sees to the
  first span after it stops, so whoever profiles a run reads the program's
  stages without opening a recording of its own.

Counters stay in :data:`repro.analysis.budget.LEDGER`; a recorder takes a
snapshot of it when it opens and when it closes.

A span never waits for the device and never moves data: it times the host
thread around the transfers and waits the program already makes.  With no
recording and no profiler it costs an annotation object and a flag check.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import jax

from repro.analysis.budget import LEDGER

__all__ = ["Record", "Recorder", "Span", "span", "recording", "traced", "PREFIX"]

#: Prefix of every span's profiler annotation.
PREFIX = "repro."
#: JAX's monitoring event around each XLA compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_Annotation = jax.profiler.TraceAnnotation


@dataclass(eq=False)
class Record:
    """One span as a recording keeps it."""

    name: str
    start: float
    end: float = math.nan
    parent: Optional["Record"] = None
    req: object = None
    compiles: int = 0
    compile_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """The spans of one recording, and ``LEDGER`` at its open and close."""

    def __init__(self) -> None:
        self.records: List[Record] = []
        self.ledger_open = LEDGER.snapshot()
        self.ledger_close: Optional[Dict[str, Dict[str, int]]] = None

    def close(self) -> None:
        self.ledger_close = LEDGER.snapshot()

    def named(self, name: str) -> List[Record]:
        """Closed records of the span ``name``."""
        return [r for r in self.records if r.name == name and not math.isnan(r.end)]

    def self_seconds(self) -> Dict[int, float]:
        """``id(record)`` -> its duration less the time its recorded
        children cover."""
        own = {id(r): r.seconds for r in self.records if not math.isnan(r.end)}
        for r in self.records:
            if r.parent is not None and id(r.parent) in own and id(r) in own:
                own[id(r.parent)] -= r.seconds
        return own

    def ledger_delta(self) -> Dict[str, Dict[str, int]]:
        """Growth of each ``LEDGER`` counter between open and close (or now,
        while open); counters that did not move are left out."""
        after = self.ledger_close if self.ledger_close is not None else LEDGER.snapshot()
        out: Dict[str, Dict[str, int]] = {}
        for section, counters in after.items():
            before = self.ledger_open.get(section, {})
            moved = {k: v - before.get(k, 0) for k, v in counters.items()
                     if v != before.get(k, 0)}
            if moved:
                out[section] = moved
        return out

    def table(self) -> List[dict]:
        """Per span name: ``n``, ``total_s``, ``self_s``, ``compiles`` and
        ``compile_s``, in order of first appearance."""
        own = self.self_seconds()
        rows: Dict[str, dict] = {}
        for r in self.records:
            if id(r) not in own:
                continue
            row = rows.setdefault(r.name, {"span": r.name, "n": 0, "total_s": 0.0,
                                           "self_s": 0.0, "compiles": 0, "compile_s": 0.0})
            row["n"] += 1
            row["total_s"] += r.seconds
            row["self_s"] += own[id(r)]
            row["compiles"] += r.compiles
            row["compile_s"] += r.compile_s
        return list(rows.values())

    def format_table(self) -> str:
        lines = [f"{'span':<24} {'n':>6} {'total_s':>10} {'self_s':>10} "
                 f"{'compiles':>8} {'compile_s':>10}"]
        for row in self.table():
            lines.append(f"{row['span']:<24} {row['n']:>6} {row['total_s']:>10.4f} "
                         f"{row['self_s']:>10.4f} {row['compiles']:>8} "
                         f"{row['compile_s']:>10.4f}")
        for section, moved in self.ledger_delta().items():
            counts = ", ".join(f"{k} +{v}" for k, v in sorted(moved.items()))
            lines.append(f"ledger {section}: {counts}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# process state
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_local = threading.local()
_open: List[Recorder] = []          # recordings opened by recording(); replaced, never mutated
_traced: Optional[Recorder] = None  # the profiler's recording
_traced_live = False
_listening = False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _sinks() -> List[Recorder]:
    """The recorders a span opened now goes to."""
    global _traced, _traced_live
    if _Annotation.is_enabled():
        if not _traced_live:
            with _lock:
                if not _traced_live:
                    _listen()
                    _traced, _traced_live = Recorder(), True
        return _open + [_traced]
    if _traced_live:
        with _lock:
            if _traced_live:
                _traced.close()
                _traced_live = False
    return _open


def _on_compile(event: str, seconds: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    for sp in reversed(_stack()):
        if sp.record is not None:
            sp.record.compiles += 1
            sp.record.compile_s += seconds
            return


def _listen() -> None:
    """Register the compile listener, once per process (call under _lock)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True


# ---------------------------------------------------------------------------
# the span
# ---------------------------------------------------------------------------


class Span:
    """A timed stage; use through :func:`span`."""

    __slots__ = ("name", "req", "start", "end", "record", "_annotation")

    def __init__(self, name: str, req: object = None):
        self.name = name
        self.req = req
        self.start = self.end = math.nan
        self.record: Optional[Record] = None

    def __enter__(self) -> "Span":
        stack = _stack()
        if self.req is None and stack:
            self.req = stack[-1].req
        if self.req is None:
            self._annotation = _Annotation(PREFIX + self.name)
        else:
            self._annotation = _Annotation(PREFIX + self.name, req=self.req)
        self._annotation.__enter__()
        sinks = _sinks()
        self.start = time.perf_counter()
        if sinks:
            parent = next((s.record for s in reversed(stack) if s.record is not None), None)
            self.record = Record(self.name, self.start, parent=parent, req=self.req)
            for rec in sinks:
                rec.records.append(self.record)
        stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        _stack().pop()
        if self.record is not None:
            self.record.end = self.end
        self._annotation.__exit__(*exc)
        return False

    @property
    def seconds(self) -> float:
        """End less start; valid once the span has closed."""
        return self.end - self.start


def span(name: str, *, req: object = None) -> Span:
    """A span ``repro.<name>``; ``req`` tags it (and its children) with a
    request id."""
    return Span(name, req)


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Keep every span opened, on any thread, until the block ends."""
    global _open
    rec = Recorder()
    with _lock:
        _listen()
        _open = _open + [rec]
    try:
        yield rec
    finally:
        with _lock:
            _open = [r for r in _open if r is not rec]
        rec.close()


def traced() -> Optional[Recorder]:
    """The recording of the latest JAX profiler trace, or None."""
    return _traced
