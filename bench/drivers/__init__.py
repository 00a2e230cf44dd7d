"""The load generator.  A traffic mix (``bench/traffic/<mix>.json``) names
its driver, ``bench/drivers/<driver>.py``, and holds that driver's
parameters; a new kind of traffic is a new driver file, found by name.

Every driver module has the same two functions:

* ``setup(sess, cfg, traffic, seed, rec) -> Window`` does everything that
  is not measured.  ``Window.step()`` then does one unit of the traffic
  through the program's entry point and returns the answers it completed;
  the harness calls it until the window's time is up.  A slice whose
  ``plan`` the window keeps is one the check compares.
* ``checked_images(cfg, traffic, seed) -> [(index, image), ...]``: images
  of the kind that a run of the mix checks, as many as it checks, for the
  control (``bench/control.py``), which puts the reference in the
  program's place.
"""

from __future__ import annotations

import dataclasses
import importlib
import random
import time
import traceback
from types import ModuleType
from typing import Callable, List, Optional

import numpy as np


def load(name: str) -> ModuleType:
    """The driver ``bench/drivers/<name>.py``."""
    return importlib.import_module(f"{__name__}.{name}")


@dataclasses.dataclass
class Slice:
    """One slice of the traffic: its image, and its plan where the check
    compares the plan's products (set in set-up or in the window)."""

    index: int
    image: np.ndarray
    plan: object = None


@dataclasses.dataclass
class Answer:
    """One result of the window, reduced to what the check compares.  The
    full segmentation is kept only for the sampled answers."""

    slice: int
    region_labels: np.ndarray
    mu: np.ndarray
    sigma: np.ndarray
    total_energy: float
    em_iters: int
    map_iters: int
    status: str
    segmentation: Optional[np.ndarray] = None


def answer(slice_index: int, r, keep: bool) -> Answer:
    return Answer(slice_index, r.region_labels, r.mu, r.sigma, r.total_energy,
                  r.em_iters, r.map_iters, r.status,
                  r.segmentation if keep else None)


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn from ``rng`` (Vitter's algorithm R): ``offer(i)`` says whether item
    ``i`` enters, and which earlier item it evicts, before the item is made.
    The check samples the window's answers so, whatever its length."""

    def __init__(self, size: int, rng: random.Random):
        self.size, self.rng, self.kept = size, rng, []

    def offer(self, i: int):
        """(enters, evicted item or None)."""
        if len(self.kept) < self.size:
            self.kept.append(i)
            return True, None
        j = self.rng.randrange(i + 1)
        if j >= self.size:
            return False, None
        evicted, self.kept[j] = self.kept[j], i
        return True, evicted


@dataclasses.dataclass
class Window:
    step: Callable[[], List[Answer]]
    slices: List[Slice]
    launch: dict  # bucket and batch of the EM launches the window drives
    unit: int     # slices one step attempts
    # The last of set-up, run with the persistent compilation cache off.
    prime: Callable[[], None] = lambda: None


def run_window(window: Window, seconds: float) -> tuple:
    """Steps until ``seconds`` have passed since the start; the last step
    begun inside the window runs to its end.  Returns (answers, attempted,
    failed, elapsed), elapsed from the window's start to the end of the
    last step.  A step that raises counts its slices as attempted and
    failed, and the window goes on."""
    answers: List[Answer] = []
    attempted = failed = 0
    t0 = time.perf_counter()
    end = t0
    while end - t0 < seconds:
        try:
            got = window.step()
        except StopIteration:
            break
        except Exception:  # noqa: BLE001 -- a failed request is counted, not fatal
            traceback.print_exc()
            got = []
            failed += window.unit
        attempted += window.unit
        answers.extend(got)
        end = time.perf_counter()
    return answers, attempted, failed, end - t0
