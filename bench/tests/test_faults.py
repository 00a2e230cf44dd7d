"""A whole run with the timed path broken underneath: ``correct`` must come
out false.  The look for a chip is skipped; everything else is the run the
command makes, at a tiny size.  One fault of each kind a one-chip cell can
have (there is no exchange between chips to leave out)."""

from __future__ import annotations

import pytest

from bench import harness
from repro.api import session as session_mod
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import pipeline as pipeline_mod

CELLS = {
    "synthetic512-g64.volume": "synthetic512-g64",
    "synthetic512-g64.solve": "synthetic512-g64",
    "synthetic512-g16-fused.solve-serial": "synthetic512-g16-fused",
}


def _run(cell, tiny, cache_dir, seconds=0.5):
    return harness.run_cell(cell, 2024, seconds, False, require_tpu=False,
                            config=tiny(CELLS[cell]), cache_dir=cache_dir)


def _state_unchanged(monkeypatch):
    """The EM executable hands back the state it was given."""
    real = session_mod.Executable.__call__

    def call(self, hoods, model, labels0, mu0, sigma0):
        res = real(self, hoods, model, labels0, mu0, sigma0)
        return res._replace(labels=labels0, mu=mu0, sigma=sigma0)

    monkeypatch.setattr(session_mod.Executable, "__call__", call)


def _half_batch(monkeypatch):
    """A batched launch solves only the first half of its lanes; the rest
    get the first lane's answer."""
    real = session_mod.Executable.__call__

    def call(self, *inputs):
        res = real(self, *inputs)
        if self.key.batch is None:
            return res
        half = self.key.batch // 2
        return em_mod.EMResult(*(leaf.at[half:].set(leaf[0]) for leaf in res))

    monkeypatch.setattr(session_mod.Executable, "__call__", call)


def _answer_altered(monkeypatch):
    """Every answer's labels inverted where the result is assembled, after
    its segmentation was drawn: an answer that says the wrong thing."""
    real = pipeline_mod._assemble_result

    def assemble(problem, result, init_s, opt_s):
        out = real(problem, result, init_s, opt_s)
        out.region_labels = 1 - out.region_labels
        return out

    monkeypatch.setattr(pipeline_mod, "_assemble_result", assemble)


def test_sound_run_is_correct(tiny, cache_dir):
    assert _run("synthetic512-g64.solve", tiny, cache_dir)["correct"] is True


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def _batched(cell: str) -> bool:
    bench = harness.load_benchmark()
    mix = next(c["traffic"] for c in bench["workloads"] if c["name"] == cell)
    return harness.load_traffic(mix).get("batch", 1) > 1


# A cell that launches one slice at a time has no batch to halve.
CASES = [(c, f) for c in sorted(CELLS) for f in FAULTS
         if f != "half_batch" or _batched(c)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, tiny, cache_dir, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(cell, tiny, cache_dir)
    assert out["correct"] is False, out["checks"]
