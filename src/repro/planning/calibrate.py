"""``python -m repro.planning.calibrate`` — the one-shot microbenchmark
pass that fits the checked-in calibration table (DESIGN.md §18).

The pass measures warm session-API executes over a small grid of the
execution axes:

* **solve grid** — single-lane run-to-convergence solves per mode over a
  size ladder (plus a K ladder on the optimized modes): fits the
  per-phase transfer/innermost-loops coefficients.
* **batched grid** — lockstep ``submit``/``drain`` launches at widths
  2/4/8 on the paper-config slice stack: fits the lane-serialization
  fraction (how much of a vmapped batch's width the platform pays in
  wall clock — ~1 on XLA:CPU, ~0 on accelerators).
* **sharded grid** — the BENCH_sharded size ladder at 1 and 8 shards:
  fits the per-MAP-iteration collective-overhead terms.  On CPU it runs
  in a child process with 8 forced host devices (the XLA device count is
  process-global, same pattern as ``benchmarks/bench_sharded.py``); on an
  accelerator this process already holds the chips, so it runs here, with
  the shard counts capped at ``jax.device_count()``.  The 1-shard rows
  double as solve observations so the sharded residuals are computed
  against timings from the same process environment.

Raw observations are stored *inside* the table, so the fit — and
therefore the table bytes — is a pure function of the file's own
contents: ``--refit`` re-runs only the (deterministic) fit from the
stored observations, which is what the calibration-table drift gate in
``benchmarks/run.py --check`` does (regenerate + ``git diff``, the same
pattern as the golden fixtures and ANALYSIS.json).  Re-*measuring*
(no ``--refit``) produces new timings and is expected to change the
bytes; that is a deliberate recalibration, reviewed like any fixture
update.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time
from typing import Callable, Dict, List

from .costmodel import (
    CostModel,
    default_table_path,
    fit_table,
    load_table,
    table_to_json,
)

#: Square image edge lengths per mode for the solve grid.  `faithful`
#: stops early (it is the slow reference composition and only needs
#: enough points to rank against the optimized modes); the optimized
#: modes extend to 288 so shard-crossover predictions at the
#: BENCH_sharded sizes interpolate instead of extrapolate.
SOLVE_SIZES: Dict[str, tuple] = {
    "faithful": (64, 96),
    "static": (64, 96, 128, 192),
    "static-pallas": (64, 96, 128, 192, 288),
}
#: (size, K) points for the K-ary ladder on the optimized modes.
K_GRID = ((96, 3), (96, 5))
#: Lockstep widths measured on the paper-config slice stack.
BATCH_WIDTHS = (2, 4, 8)
#: Sharded ladder (matches benchmarks/bench_sharded.py SIZES).
SHARD_SIZES = (96, 192, 288)
SHARD_COUNTS = (1, 8)
SHARD_MODE = "static-pallas"   # the serving-path mode (DESIGN.md §16)


def _grid(size: int) -> tuple:
    return (size // 8, size // 8)


def _round6(x: float) -> float:
    return float(f"{x:.6g}")


def _time(fn: Callable[[], object], repeats: int = 3) -> float:
    """Warm-path median: one unmeasured call, then the median of
    ``repeats`` (the executable cache makes every call a pure replay)."""
    fn()
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return sorted(samples)[len(samples) // 2]


def _image(size: int, k: int):
    import numpy as np

    from repro.core import synthetic

    if k == 2:
        vol = synthetic.make_synthetic_volume(seed=0, n_slices=1, shape=(size, size))
    else:
        vol = synthetic.make_kary_volume(
            seed=0, n_slices=1, shape=(size, size), n_phases=k
        )
    return np.asarray(vol.images[0])


def _solve_obs(mode: str, size: int, k: int, shards: int = 1) -> Dict:
    from repro import api

    sess = api.Segmenter(
        api.ExecutionConfig(
            overseg_grid=_grid(size), mode=mode, n_labels=k, shards=shards
        )
    )
    plan = sess.plan(_image(size, k))
    sess.compile(plan)   # pay the compile outside the timer
    res = sess.execute(plan, seed=0)
    t = _time(lambda: sess.execute(plan, seed=0))
    cap, nh, nr = plan.bucket
    obs = {
        "kind": "sharded" if shards > 1 else "solve",
        "mode": mode, "cap": cap, "nh": nh, "nr": nr, "k": k,
        "em_iters": int(res.em_iters), "map_iters": int(res.map_iters),
        "seconds": _round6(t),
    }
    if shards > 1:
        obs["shards"] = shards
    return obs


def _batched_obs(width: int) -> Dict:
    import numpy as np

    from repro import api
    from repro.api.session import BucketKey
    from repro.configs.pmrf_paper import CONFIG
    from repro.core import synthetic

    vol = synthetic.make_synthetic_volume(
        seed=0, n_slices=max(CONFIG.synthetic_slices, width),
        shape=CONFIG.synthetic_shape, gaussian_sigma=CONFIG.gaussian_sigma,
    )
    imgs = [np.asarray(im) for im in vol.images[:width]]
    sess = api.Segmenter(api.ExecutionConfig(overseg_grid=(16, 16)))
    plans = [sess.plan(img) for img in imgs]
    joint = BucketKey(
        *(max(b[d] for b in (p.bucket for p in plans)) for d in range(3))
    )

    def run():
        for p in plans:
            sess.submit(p, seed=0, bucket=joint)
        return sess.drain()

    results = run()   # pays the batch-width compile
    t = _time(run)
    return {
        "kind": "batched", "mode": sess.config.mode,
        "cap": joint.capacity, "nh": joint.n_hoods, "nr": joint.n_regions,
        "k": sess.config.n_labels, "width": width,
        # The lockstep program runs every lane to the slowest lane's
        # convergence — the max-lane counts are what the launch executes.
        "em_iters": int(max(r.em_iters for r in results)),
        "map_iters": int(max(r.map_iters for r in results)),
        "seconds": _round6(t),
    }


def _sharded_ladder(shard_counts=SHARD_COUNTS) -> List[Dict]:
    """The BENCH_sharded ladder at each shard count (in the 8-device child
    on CPU).  1-shard rows are plain solve observations."""
    obs = []
    for size in SHARD_SIZES:
        for shards in shard_counts:
            obs.append(_solve_obs(SHARD_MODE, size, 2, shards=shards))
    return obs


def _run_sharded_child() -> List[Dict]:
    from repro.xla_env import force_host_device_count

    root = pathlib.Path(__file__).resolve().parents[3]
    env = force_host_device_count(max(SHARD_COUNTS), dict(os.environ))
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.planning.calibrate", "--sharded-child"],
        capture_output=True, text=True, env=env, cwd=root, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded calibration child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


def collect_observations(*, sharded: bool = True) -> List[Dict]:
    obs: List[Dict] = []
    for mode, sizes in SOLVE_SIZES.items():
        for size in sizes:
            obs.append(_solve_obs(mode, size, 2))
            print(f"  solve {mode} {size}x{size}: {obs[-1]['seconds']}s",
                  file=sys.stderr)
    for size, k in K_GRID:
        for mode in ("static", "static-pallas"):
            obs.append(_solve_obs(mode, size, k))
            print(f"  solve {mode} {size}x{size} K={k}: {obs[-1]['seconds']}s",
                  file=sys.stderr)
    for width in BATCH_WIDTHS:
        obs.append(_batched_obs(width))
        print(f"  batched width={width}: {obs[-1]['seconds']}s", file=sys.stderr)
    if sharded:
        import jax

        # One process per chip: only the CPU backend measures in a child.
        if jax.default_backend() == "cpu":
            ladder = _run_sharded_child()
        else:
            ladder = _sharded_ladder(
                tuple(sorted({1, min(max(SHARD_COUNTS), jax.device_count())}))
            )
        obs.extend(ladder)
        print(f"  sharded ladder: {len(ladder)} points", file=sys.stderr)
    return obs


def refit(path: pathlib.Path) -> str:
    """Deterministic refit from the table's own stored observations (the
    drift-gate path — byte-identical output for an untampered table)."""
    table = load_table(path)
    return table_to_json(fit_table(table["observations"], table["meta"]))


def _summarize(table: Dict) -> None:
    model = CostModel(table)
    pr = table["priors"]
    print(
        f"fitted: serial_frac={table['width']['serial_frac']} "
        f"iter_cv={pr['iter_cv']} mean_em_iters={pr['mean_em_iters']:.2f}",
        file=sys.stderr,
    )
    # At-a-glance sanity check of the shard routing this table produces,
    # one line per distinct sharded-observation bucket.
    seen = set()
    for o in table["observations"]:
        if o["kind"] != "sharded":
            continue
        bucket = (o["cap"], o["nh"], o["nr"])
        if bucket in seen:
            continue
        seen.add(bucket)
        d = model.choose_shards(
            mode=o["mode"], bucket=bucket, candidates=SHARD_COUNTS
        )
        print(f"  bucket {bucket}: choose_shards -> {d.shards} "
              f"{d.as_dict()['predicted_seconds']}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.planning.calibrate", description=__doc__
    )
    ap.add_argument(
        "--out", type=pathlib.Path, default=default_table_path(),
        help="table path (default: the checked-in src/repro/planning/calibration.json)",
    )
    ap.add_argument(
        "--refit", action="store_true",
        help="re-fit from the stored observations only (deterministic; "
             "the drift gate's path) instead of re-measuring",
    )
    ap.add_argument(
        "--no-sharded", action="store_true",
        help="skip the sharded child pass (collective terms keep their "
             "previous/default values of zero)",
    )
    ap.add_argument("--sharded-child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.sharded_child:
        print(json.dumps(_sharded_ladder()))
        return

    if args.refit:
        args.out.write_text(refit(args.out))
        print(f"refit from stored observations -> {args.out}", file=sys.stderr)
        return

    import jax

    meta = {
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "source": "calibrate",
        "grid": {
            "solve_sizes": {m: list(s) for m, s in SOLVE_SIZES.items()},
            "k_grid": [list(p) for p in K_GRID],
            "batch_widths": list(BATCH_WIDTHS),
            "shard_sizes": list(SHARD_SIZES),
            "shard_counts": list(SHARD_COUNTS),
        },
    }
    print(f"calibrating on platform={meta['platform']} ...", file=sys.stderr)
    obs = collect_observations(sharded=not args.no_sharded)
    table = fit_table(obs, meta)
    args.out.write_text(table_to_json(table))
    print(f"{len(obs)} observations -> {args.out}", file=sys.stderr)
    _summarize(table)


if __name__ == "__main__":
    main()
