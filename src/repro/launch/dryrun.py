import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input-shape) cell against the
production meshes — 16x16 (single pod, 256 chips) and 2x16x16 (two pods,
512 chips) — from ShapeDtypeStructs only (no allocation), then records::

    compiled.memory_analysis()   -> per-chip bytes (proves it fits)
    compiled.cost_analysis()     -> per-chip FLOPs / HBM bytes
    parse_collectives(hlo text)  -> per-chip collective bytes by op

into one JSON artifact per cell under ``artifacts/dryrun/`` at the repo
root; ``tests/test_dryrun.py`` checks them once generated.

The two module-level lines above MUST stay the first statements: JAX locks
the device count at first backend init, and only the dry-run may see the
512 placeholder devices (tests/benches keep the 1 real CPU device).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, analytic_flash_traffic, model_flops_for
from repro.launch.specs import build_step, runnable_cells

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def artifact_path(arch: str, shape: str, multi_pod: bool, variant: str = "") -> Path:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    sub = ARTIFACT_DIR if not variant else ARTIFACT_DIR.parent / f"dryrun_{variant}"
    return sub / f"{arch}__{shape}__{mesh_tag}.json"


def _apply_overrides(cfg, overrides: dict):
    import dataclasses
    if not overrides:
        return cfg
    return dataclasses.replace(cfg, **overrides)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, force: bool = False,
             variant: str = "", overrides: dict | None = None,
             microbatches: int = 1) -> dict:
    cfg = _apply_overrides(get_config(arch), overrides or {})
    out_path = artifact_path(arch, shape_name, multi_pod, variant)
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    if shape_name in cfg.skip_shapes:
        rec = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skip(full-attn)",
        }
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    shape = SHAPES[shape_name]
    t0 = time.perf_counter()
    from repro.training.train_step import TrainStepConfig
    cell = build_step(
        cfg, shape_name, mesh,
        ts_cfg=TrainStepConfig(microbatches=microbatches),
    )
    with mesh:
        lowered = cell.fn.lower(*cell.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # loop-aware per-chip cost from the partitioned HLO (cost_analysis
    # counts while bodies once — see launch/hlo_cost.py docstring)
    totals = hlo_cost.analyze(compiled.as_text())

    tokens = shape.global_batch * (shape.seq_len if cell.kind != "decode" else 1)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    rf = Roofline(
        flops_per_chip=totals.flops,
        hbm_bytes_per_chip=totals.hbm_bytes,
        coll_bytes_per_chip=totals.coll_total_bytes,
        n_chips=n_chips,
        model_flops=model_flops_for(
            cell.kind, cell.n_params, cell.n_active_params, tokens
        ),
        flash_bytes_per_chip=totals.flash_bytes,
        kernel_flash_bytes=analytic_flash_traffic(
            cfg, shape, mesh_shape, cell.kind
        ),
    )
    top_dots = sorted(
        totals.dot_flops_by_shape.items(), key=lambda kv: -kv[1]
    )[:8]

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": cell.kind,
        "status": "ok",
        "n_params": cell.n_params,
        "n_active_params": cell.n_active_params,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes_estimate": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)
            ),
        },
        "collectives": {
            "bytes_by_op": {k: v for k, v in totals.coll_bytes.items()},
            "count_by_op": {k: v for k, v in totals.coll_count.items()},
            "total_bytes": totals.coll_total_bytes,
        },
        "cost_analysis_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "top_dots": [{"shape": k, "flops": v} for k, v in top_dots],
        "roofline": rf.as_dict(),
    }
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), help="one architecture")
    ap.add_argument("--shape", choices=sorted(SHAPES), help="one shape")
    ap.add_argument("--all", action="store_true", help="every runnable cell")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute artifacts")
    ap.add_argument("--variant", default="", help="artifact-dir tag for config variants")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument(
        "--set", action="append", default=[], metavar="FIELD=VALUE",
        help="ModelConfig override, e.g. --set remat_policy=dots_nb",
    )
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    assert jax.device_count() == 512, (
        f"dry-run needs 512 placeholder devices, got {jax.device_count()} — "
        "was another jax user initialized first?"
    )

    cells = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for multi_pod in meshes:
        for arch, shape in cells:
            tag = f"{arch} x {shape} x {'2x16x16' if multi_pod else '16x16'}"
            try:
                rec = run_cell(
                    arch, shape, multi_pod=multi_pod, force=args.force,
                    variant=args.variant, overrides=overrides,
                    microbatches=args.microbatches,
                )
            except Exception as e:  # a failure here is a sharding bug
                failures.append(tag)
                print(f"[FAIL] {tag}: {e}")
                traceback.print_exc()
                continue
            if rec["status"].startswith("skip"):
                print(f"[skip] {tag}: {rec['status']}")
                continue
            r = rec["roofline"]
            print(
                f"[ ok ] {tag}: kind={rec['kind']} "
                f"compile={rec['compile_s']:.1f}s "
                f"compute={r['compute_s']*1e3:.2f}ms "
                f"memory={r['memory_s']*1e3:.2f}ms "
                f"collective={r['collective_s']*1e3:.2f}ms "
                f"bound={r['bound']} "
                f"peak={rec['memory']['peak_bytes_estimate']/2**30:.2f}GiB/chip"
            )
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print("dry-run complete.")


if __name__ == "__main__":
    main()
