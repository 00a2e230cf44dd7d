"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure + the framework benches:

    table1              serial vs DPP-PMRF runtime (paper Table 1)
    fig3                coarse-parallel reference vs DPP (paper Fig. 3)
    fig4                per-DPP breakdown + size scaling (paper Fig. 4)
    faithful_vs_static  beyond-paper sort-hoisting ablation
    pmrf                per-mode EM timing on the paper config; emits
                        BENCH_pmrf.json for cross-PR perf tracking
    api                 session API: cold-compile vs warm-cache latency and
                        batched vs serial throughput; emits BENCH_api.json
    sharded             multi-device EM: 1 vs 8 shards, static and
                        static-pallas; emits BENCH_sharded.json
    serve               serving engine: serial vs lockstep-batched vs
                        continuous ticked batching; emits BENCH_serve.json
    kernels             Pallas kernels vs jnp oracles
    roofline            (arch x shape) roofline table from the dry-run

Pass section names to run a subset: ``python -m benchmarks.run table1 fig3``.

``--check`` turns each section's regression gates into hard assertions
(``benchmarks.common.CHECK``): a gated comparison that regresses — e.g. the
autotuned ``segment_volume`` batch="auto" plan losing to the best fixed
config by more than 10% (bench_pmrf), or the ``--shards auto`` choice
losing a cell of the sharded size sweep (bench_sharded) — fails the run
instead of only being reported.  ``--check`` also runs the
calibration-table drift gate: the checked-in
``src/repro/planning/calibration.json`` must refit byte-identically from
its own stored observations (DESIGN.md §18).
"""

from __future__ import annotations

import sys
import time
import traceback

SECTIONS = (
    "table1", "fig3", "fig4", "faithful_vs_static", "pmrf", "api", "sharded",
    "serve", "kernels", "roofline",
)


def _check_calibration_drift() -> None:
    """The drift gate (DESIGN.md §18): the checked-in calibration table is
    a pure function of its own stored observations, so refitting must
    reproduce the file byte-for-byte.  Drift means a stale fit or a hand
    edit — the autotuner gates above would be vouching for a table nobody
    can regenerate."""
    from repro.planning import costmodel as planning

    table = planning.load_table()
    refit = planning.fit_table(table["observations"], table["meta"])
    if planning.table_to_json(refit) != planning.default_table_path().read_text():
        raise AssertionError(
            "calibration-table drift: src/repro/planning/calibration.json "
            "does not refit from its own stored observations; regenerate "
            "with PYTHONPATH=src python -m repro.planning.calibrate --refit"
        )
    print("calibration table: refit reproduces the checked-in bytes")


def main() -> None:
    args = sys.argv[1:]
    if "--check" in args:
        from benchmarks import common

        common.CHECK = True
        args = [a for a in args if a != "--check"]
    want = args or list(SECTIONS)
    from repro.xla_env import use_compile_cache

    use_compile_cache()
    failures = []
    for name in want:
        assert name in SECTIONS, f"unknown section {name!r}; have {SECTIONS}"
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["main"])
        print(f"===== {name} =====")
        t0 = time.perf_counter()
        try:
            mod.main()
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"===== {name} done in {time.perf_counter()-t0:.1f}s =====\n")
    from benchmarks import common

    if common.CHECK:
        print("===== calibration drift gate =====")
        try:
            _check_calibration_drift()
        except Exception:
            failures.append("calibration-drift")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
