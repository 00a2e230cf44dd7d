"""Derive a configuration's bucket grid from plans of many seeds (CPU).

Plans slice 0..n-1 of each seed's volume through ``Segmenter.plan`` and
prints, as one JSON object, the distribution of the plans' natural shapes
(hood elements, hoods, regions) and the bucket that the configuration's
``capacity_bucket`` / ``segment_bucket`` give each.  Run from the repo root::

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/derive_buckets.py \\
        synthetic512-g64 --seeds 0-23 --slices 2
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seeds", default="0-23", help="a-b, inclusive")
    ap.add_argument("--slices", type=int, default=2)
    args = ap.parse_args(argv)
    lo, hi = (int(x) for x in args.seeds.split("-"))

    import numpy as np

    from bench import harness, synthetic

    cfg = harness.load_config(args.config)
    sess = harness.make_session(cfg)
    rows = []
    for seed in range(lo, hi + 1):
        images, _ = synthetic.make_slices(seed, args.slices, cfg["shape"], cfg["corruption"])
        for i, img in enumerate(np.asarray(images)):
            t0 = time.perf_counter()
            plan = sess.plan(img)
            h = plan.problem.hoods
            rows.append({"seed": seed, "slice": i, "capacity": h.capacity,
                         "n_hoods": h.n_hoods, "n_regions": h.n_regions,
                         "n_elements": h.n_elements, "bucket": list(plan.bucket),
                         "plan_s": round(time.perf_counter() - t0, 2)})
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    out = {"config": args.config, "seeds": args.seeds, "slices_per_seed": args.slices}
    for k in ("capacity", "n_hoods", "n_regions", "n_elements"):
        v = np.array([r[k] for r in rows])
        out[k] = {"min": int(v.min()), "median": float(np.median(v)), "max": int(v.max())}
    out["buckets"] = sorted({tuple(r["bucket"]) for r in rows})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
