"""The full-slice cell's own pieces at a tiny size on the CPU: the clique
candidate counter's reader, a run of the cell's traffic at a tiny shape,
and its control."""

from __future__ import annotations

import pytest

from bench import control, harness
from repro import obs
from repro.analysis import budget

CELL = "fullslice1813x1830-g227x229.volume-fullslice"


def _tiny() -> dict:
    cfg = harness.load_config("fullslice1813x1830-g227x229")
    cfg.update(shape=[61, 67], overseg_grid=[7, 8], capacity_bucket=4096, segment_bucket=64)
    return cfg


def _run(recorder):
    run = harness.Run(config={}, spans=[], compiles=[], answers=[], completed=2,
                      window_s=1.0, launch={}, plans=[])
    run.recorder = recorder
    return run


def test_clique_candidates_reader():
    read = harness.load_reader("plan.clique_candidates_per_slice")
    rec = obs.Recorder()
    for start in (0.0, 5.0):
        rec.records.append(obs.Record("plan", start, start + 1.0))
    assert read(_run(rec)) is None  # no candidates counted: a program without them
    budget.LEDGER.bump("plan", "clique_candidates", 300)
    rec.close()
    assert read(_run(rec)) == pytest.approx(150.0)


def test_tiny_cell_is_correct_and_its_control_is_not(cache_dir):
    seed = 2**33 + 7
    good = control.readings(CELL, seed, 1.0, False, require_tpu=False, config=_tiny(),
                            cache_dir=cache_dir)
    assert good["correct"] is True, good
    bad = control.readings(CELL, seed, 1.0, True, require_tpu=False, config=_tiny(),
                           cache_dir=cache_dir)
    assert bad["correct"] is False, bad
