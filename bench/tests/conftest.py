"""CPU tests of the benchmark at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They never need a chip: the harness's look for one is skipped where a test
drives a whole run (``require_tpu=False``).
"""

from __future__ import annotations

import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

TINY_GRID = {"synthetic512-g64": [8, 8], "synthetic512-g16-fused": [4, 4]}


def tiny_config(name: str) -> dict:
    """A configuration's file cut to 64x64 slices, for the CPU."""
    cfg = harness.load_config(name)
    cfg.update(shape=[64, 64], overseg_grid=TINY_GRID[name],
               capacity_bucket=4096, segment_bucket=64)
    return cfg


@pytest.fixture
def tiny(monkeypatch):
    """``tiny(name)``: the tiny configuration; the fused kernel, where the
    configuration states it, runs in the Pallas interpreter."""

    def make(name: str) -> dict:
        cfg = tiny_config(name)
        if cfg["mode"] == "static-pallas":
            monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")
        return cfg

    return make


@pytest.fixture
def cache_dir(tmp_path):
    return tmp_path / "jax_cache"
