"""Sharded-vs-single-device EM timing through the session API.

Measures the same problem at ``shards=1`` and ``shards=8`` for the two
optimized execution modes (static, static-pallas) and emits
``BENCH_sharded.json`` for cross-PR perf tracking of the multi-device
path (DESIGN.md §11).  Also asserts the sharded segmentation is
bit-identical to the single-device one — the benchmark doubles as a
cheap end-to-end parity check.

On CPU the XLA device count is process-global and fixed at backend init,
so the measurement runs in a child process launched with
``--xla_force_host_platform_device_count=8``; the parent forwards the
child's JSON.  On CPU the 8 "devices" share the machine's cores, so the
sharded timings measure collective/partitioning overhead, not speedup —
the number to watch off-TPU is the overhead ratio.  On an accelerator the
parent already holds the chips, so it measures in-process, with the shard
counts capped at ``jax.device_count()``.

The size sweep also records the calibrated cost model's shard choice per
size (``--shards auto``, DESIGN.md §18); under ``--check`` the parent
gates that the chosen shard count's measured time stays within 10% of
the best fixed shard count in every cell of the sweep.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

OUT_PATH = pathlib.Path("BENCH_sharded.json")
MODES = ("static", "static-pallas")
SHARDS = (1, 8)
#: Square image edge lengths for the size sweep.  The base size keeps the
#: historical BENCH_sharded numbers comparable; the larger sizes track how
#: partitioning overhead amortizes as the per-shard work grows.  The
#: oversegmentation grid scales with the image (one cell per 8x8 tile) so
#: every size runs at the same region granularity.
BASE_SIZE = 96
SIZES = (96, 192, 288)


def _grid(size: int):
    return (size // 8, size // 8)


def _measure(shard_counts=SHARDS) -> dict:
    import jax
    import numpy as np

    from benchmarks.common import time_fn
    from repro import api
    from repro.core import synthetic

    def image(size):
        vol = synthetic.make_synthetic_volume(
            seed=0, n_slices=1, shape=(size, size)
        )
        return np.asarray(vol.images[0])

    def sweep(img, size, modes):
        """mode x shards timing at one size, with the parity assert."""
        per_mode = {}
        for mode in modes:
            per = {}
            segmentations = {}
            for shards in shard_counts:
                sess = api.Segmenter(
                    api.ExecutionConfig(
                        overseg_grid=_grid(size), mode=mode, shards=shards
                    )
                )
                plan = sess.plan(img)
                exe = sess.compile(plan)  # pay the compile outside the timer
                res = sess.execute(plan, seed=0)
                segmentations[shards] = np.asarray(res.segmentation)
                t = time_fn(lambda: sess.execute(plan, seed=0), repeats=3)
                per[str(shards)] = {
                    "optimize_seconds": round(t, 5),
                    "compile_seconds": round(exe.compile_seconds, 3),
                    "em_iters": int(res.em_iters),
                }
                if shards == 1:
                    # The cost-model shard routing for this size
                    # (--shards auto, DESIGN.md §18); the parent's
                    # --check gate holds the chosen count within 10% of
                    # the measured-best fixed count.
                    per["autotune"] = sess.cost_model().choose_shards(
                        mode=mode, bucket=plan.bucket, candidates=shard_counts,
                        max_em_iters=sess.config.max_em_iters,
                        max_map_iters=sess.config.max_map_iters,
                    ).as_dict()
            match = bool(
                (segmentations[min(shard_counts)]
                 == segmentations[max(shard_counts)]).all()
            )
            per["labels_match"] = match
            assert match, (
                f"sharded {mode} segmentation diverged from single-device "
                f"at size {size}"
            )
            per_mode[mode] = per
        return per_mode

    out = {
        "jax_backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "shard_counts": list(shard_counts),
        "image_shape": [BASE_SIZE, BASE_SIZE],
        "modes": sweep(image(BASE_SIZE), BASE_SIZE, MODES),
        # Size sweep on the fused static-pallas route only: it is the
        # serving-path mode, and the static row at BASE_SIZE above already
        # anchors the cross-mode comparison.
        "sizes": {
            str(size): {
                "overseg_grid": list(_grid(size)),
                **sweep(image(size), size, ("static-pallas",))["static-pallas"],
            }
            for size in SIZES
        },
    }
    return out


def _measure_in_child() -> dict:
    """CPU only: measure in a child with forced host devices (this
    process's device count is already fixed)."""
    from repro.xla_env import force_host_device_count

    root = pathlib.Path(__file__).resolve().parent.parent
    env = force_host_device_count(max(SHARDS), dict(os.environ))
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--child"],
        capture_output=True, text=True, env=env, cwd=root, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded bench child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


def main() -> None:
    if "--child" in sys.argv:
        print(json.dumps(_measure()))
        return

    import jax

    # One process per chip: a child could not reach the accelerator this
    # process holds, so only the CPU backend spawns one.
    if jax.default_backend() == "cpu":
        result = _measure_in_child()
    else:
        result = _measure(tuple(sorted({1, min(max(SHARDS), jax.device_count())})))
    shard_counts = result["shard_counts"]
    OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")

    from benchmarks.common import print_csv

    rows = []
    for mode, per in result["modes"].items():
        for shards in map(str, shard_counts):
            d = per[shards]
            rows.append((mode, shards, d["optimize_seconds"],
                         d["compile_seconds"], per["labels_match"]))
    print_csv(
        f"sharded EM: 1 vs {max(shard_counts)} shards "
        f"({result['jax_backend']}, {result['device_count']} devices) -> {OUT_PATH}",
        ["mode", "shards", "optimize_s", "compile_s", "labels_match"],
        rows,
    )
    size_rows = []
    for size, per in result["sizes"].items():
        for shards in map(str, shard_counts):
            d = per[shards]
            size_rows.append((size, shards, d["optimize_seconds"],
                              d["em_iters"], per["labels_match"]))
    print_csv(
        "sharded EM size sweep (static-pallas)",
        ["size", "shards", "optimize_s", "em_iters", "labels_match"],
        size_rows,
    )

    from benchmarks import common

    if common.CHECK:
        # The shard-autotuner gate (DESIGN.md §18): at every size in the
        # sweep the cost model's chosen shard count must measure within
        # 10% of the best fixed shard count — the model is allowed to be
        # wrong about absolute seconds, not about the ranking.
        for size, per in result["sizes"].items():
            chosen = per["autotune"]["shards"]
            measured = {s: per[str(s)]["optimize_seconds"] for s in shard_counts}
            best = min(measured.values())
            assert measured[chosen] <= best * 1.10, (
                f"shard autotuner regressed at size {size}: chose "
                f"{chosen} shards ({measured[chosen]}s) vs best fixed "
                f"{best}s (measured {measured}; decision {per['autotune']})"
            )


if __name__ == "__main__":
    main()
