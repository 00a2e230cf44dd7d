"""DPP-PMRF segmentation driver (the paper's own application).

Generates (or loads) a corrupted porous-media volume and runs it through
the session API (``repro.api.Segmenter``, DESIGN.md §10): per-slice plans
are submitted and drained as one micro-batched launch, and ``--repeat``
re-runs the volume through the same session so the warm executable-cache
path is exercisable from the command line (repeat > 1 should show the
first run paying the compile and the rest reusing it).

Reports the paper's verification metrics (precision/recall/accuracy/
porosity), phase timings, and the session's cache statistics.

Multi-device: ``--shards N`` block-partitions each slice's hood elements
over an N-device mesh (DESIGN.md §11).  On CPU the devices are virtual —
the launcher injects ``--xla_force_host_platform_device_count=N`` into
``XLA_FLAGS`` before JAX initializes, so plain
``python -m repro.launch.segment --shards 8`` works on a laptop.

Usage::

    PYTHONPATH=src python -m repro.launch.segment --slices 2 --size 96 \
        --mode static --backend auto --repeat 3 --dataset synthetic
    PYTHONPATH=src python -m repro.launch.segment --shards 8 --mode static
    PYTHONPATH=src python -m repro.launch.segment --spans  # per-span table on stderr
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--size", type=int, default=96)
    ap.add_argument("--grid", type=int, default=12, help="oversegmentation grid")
    ap.add_argument(
        "--mode", choices=("static", "faithful", "static-pallas"), default="static"
    )
    ap.add_argument(
        "--labels", type=int, default=2, metavar="K",
        help="label count K (K-ary multi-label segmentation, DESIGN.md §13); "
        "K>2 generates a K-phase synthetic volume and reports multi-class "
        "accuracy",
    )
    ap.add_argument(
        "--backend",
        choices=("auto", "xla", "pallas-tpu", "pallas-interpret"),
        default="auto",
        help="kernel dispatch backend (DESIGN.md §3)",
    )
    ap.add_argument(
        "--repeat", type=int, default=1,
        help="run the volume N times through one session (N>1 exercises the "
        "warm executable cache; timings per repeat are printed)",
    )
    ap.add_argument(
        "--batch", choices=("auto", "always", "never"), default="auto",
        help="micro-batch slices via submit/drain; auto batches only where "
        "it pays (accelerators, bounded capacity spread)",
    )
    ap.add_argument(
        "--shards", type=int, default=1,
        help="block-partition hood elements over an N-device mesh; on CPU "
        "this forces N virtual host devices (usable anywhere)",
    )
    ap.add_argument("--dataset", choices=("synthetic", "experimental"),
                    default="synthetic")
    ap.add_argument("--init", choices=("random", "quantile"), default="quantile")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--spans", action="store_true",
        help="record the program's spans (repro.obs) and print, to stderr, "
        "one row per span name: count, total and self seconds, and the XLA "
        "compiles that ran in it; then the growth of the LEDGER counters",
    )
    args = ap.parse_args()

    # The XLA device count is fixed at backend init, so virtual host
    # devices must be forced before the first jax import (repro.xla_env
    # docstring).
    if args.shards > 1:
        from repro.xla_env import force_host_device_count

        force_host_device_count(args.shards)
    from repro.xla_env import use_compile_cache

    use_compile_cache()

    from repro import obs

    with obs.recording() if args.spans else contextlib.nullcontext() as rec:
        _run(ap, args)
    if rec is not None:
        print(rec.format_table(), file=sys.stderr)


def _run(ap, args) -> None:
    from repro import api
    from repro.core import metrics as M
    from repro.core import synthetic as S

    if args.labels > 2 and args.dataset == "experimental":
        ap.error(
            "--labels K>2 generates its own K-phase volume and cannot be "
            "combined with --dataset experimental"
        )
    if args.labels > 2:
        vol = S.make_kary_volume(
            seed=args.seed, n_slices=args.slices, shape=(args.size, args.size),
            n_phases=args.labels,
        )
    elif args.dataset == "synthetic":
        vol = S.make_synthetic_volume(
            seed=args.seed, n_slices=args.slices, shape=(args.size, args.size)
        )
    else:
        vol = S.make_experimental_like_volume(
            seed=args.seed, n_slices=args.slices, shape=(args.size, args.size)
        )
    images = [np.asarray(im) for im in vol.images]

    sess = api.Segmenter(api.ExecutionConfig(
        backend=args.backend,
        mode=args.mode,
        init=args.init,
        overseg_grid=(args.grid, args.grid),
        n_labels=args.labels,
        shards=args.shards,
    ))

    results = None
    for r in range(max(1, args.repeat)):
        t0 = time.perf_counter()
        results, mean_opt = sess.segment_stack(
            images, seed=args.seed, batch=args.batch
        )
        wall = time.perf_counter() - t0
        print(json.dumps({
            "repeat": r,
            "wall_s": round(wall, 3),
            "mean_optimize_s": round(mean_opt, 3),
            "cache": sess.stats.as_dict(),
        }))

    per_slice = []
    for i, res in enumerate(results):
        gt = np.asarray(vol.ground_truth[i])
        if args.labels > 2:
            m = {"accuracy": M.multiclass_accuracy(res.segmentation, gt, args.labels)}
        else:
            m = M.evaluate(res.segmentation, gt).as_dict()
        per_slice.append(
            {
                "slice": i,
                **{k: round(v, 4) for k, v in m.items()},
                "em_iters": res.em_iters,
                "map_iters": res.map_iters,
                "init_s": round(res.init_seconds, 3),
                "optimize_s": round(res.optimize_seconds, 3),
            }
        )
        print(json.dumps(per_slice[-1]))

    acc = float(np.mean([p["accuracy"] for p in per_slice]))
    opt = float(np.mean([p["optimize_s"] for p in per_slice]))
    print(json.dumps({
        "mean_accuracy": round(acc, 4),
        "mean_optimize_s": round(opt, 3),
        "labels": args.labels,
        "backend": sess.config.resolved_backend(),
        "shards": sess.config.shards,
        "executables_cached": len(sess.cache_keys),
    }))


if __name__ == "__main__":
    main()
