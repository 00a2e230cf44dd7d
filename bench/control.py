"""Readings that the check's limits are set from (not run by the benchmark).

For one cell, on each seed given, in one process on the chip:

* ``program``: the readings of a sound run, a short window at the cell's
  own load and size (the numbers ``bench/check.py`` compares);
* ``control``: the same numbers for the control, which the check must
  refuse.  Where the configuration states float32 and the program has a
  bfloat16 path of its own (``static-pallas``), the control is the program
  with that path on; otherwise it is the reference computed in bfloat16,
  put in the program's place.

Each line carries ``correct``, the cell's limits (``bench/limits/<cell>.json``)
applied to its numbers as a run applies them: true for the program, false
for the control.

    python3 bench/control.py --workload <cell> --control-seeds 1,2,3 \
        [--seeds 4,5,6 --seconds 2]

Prints one JSON line per seed and reading, then a summary line with the
largest program reading and the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
from types import SimpleNamespace

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import check, drivers, harness, reference  # noqa: E402


def reference_answers(cfg: dict, traffic: dict, seed: int, dtype: str):
    """The reference at ``dtype`` in the program's place: its answers and
    plan products for images of the kind, and as many, as a run of the mix
    checks."""
    slices, answers = [], []
    for i, image in drivers.load(traffic["driver"]).checked_images(cfg, traffic, seed):
        ref = reference.segment(image, cfg, dtype)
        hoods = SimpleNamespace(n_hoods=ref.n_hoods, n_elements=ref.n_elements)
        plan = SimpleNamespace(problem=SimpleNamespace(labels_px=ref.superpixels, hoods=hoods))
        slices.append(drivers.Slice(i, image, plan))
        answers.append(drivers.Answer(i, ref.region_labels, ref.mu, ref.sigma,
                                      ref.total_energy, ref.em_iters, ref.map_iters,
                                      "converged", ref.segmentation))
    return answers, slices


def readings(workload: str, seed: int, seconds: float, control: bool, *,
             require_tpu: bool = True, config=None, cache_dir=harness.CACHE_DIR) -> dict:
    bench = harness.load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == workload)
    cfg = dict(config or harness.load_config(cell["config"]))
    traffic = harness.load_traffic(cell["traffic"])
    if control and cfg["mode"] != "static-pallas":
        answers, slices = reference_answers(cfg, traffic, seed, "bf16")
        numbers = check.compare(answers, slices, cfg)
        return {"correct": check.within(numbers, harness.load_limits(workload)), **numbers}
    if control:
        cfg["precision"] = "bf16"
    out = harness.run_cell(workload, seed, seconds, False, require_tpu=require_tpu,
                           config=cfg, cache_dir=cache_dir)
    return {"correct": out["correct"], **{k: v["value"] for k, v in out["checks"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="program readings, comma-separated")
    ap.add_argument("--control-seeds", required=True, help="control readings")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",")]
    worst = {}
    for kind, ss in (("program", seeds), ("control", cseeds)):
        for seed in ss:
            r = readings(args.workload, seed, args.seconds, kind == "control")
            print(json.dumps({"kind": kind, "seed": seed, **r}), flush=True)
            for k, v in r.items():
                if k == "correct":
                    continue
                agg = max if kind == "program" else min
                worst.setdefault(kind, {})[k] = agg(worst.get(kind, {}).get(k, v), v)
    summary = {"workload": args.workload, "program_max": worst.get("program"),
               "control_min": worst.get("control")}
    print(json.dumps(summary, default=lambda x: None if not math.isfinite(x) else x))
    return 0


if __name__ == "__main__":
    sys.exit(main())
