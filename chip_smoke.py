"""Smoke test of the segmentation path on a TPU.

Run from the repository root (no ``PYTHONPATH`` needed)::

    python chip_smoke.py              # phases (a)-(c) on one chip
    python chip_smoke.py --chips 4    # phase (d) only, on a 2x2 host

It drives the normal entry points (``repro.api.Segmenter`` and
``repro.serving.SegmentationEngine``) at the paper's synthetic slice size,
512x512 (the 512^3 volume of arXiv 1809.05018 §4.1.1):

(a) volume:  4 slices through ``segment_stack`` in ``static`` mode at one
    region per 8x8 tile; accuracy against the ground truth, and slice 0's
    labels against the NumPy oracle ``reference.golden_em``.
(b) fused:   one slice in ``static-pallas`` mode at a bucket inside the
    fused kernel's one-hot guard; the compiled program must hold the fused
    EM-tick kernel, and its labels are compared with ``static`` mode's.
(c) serving: 8 requests through ``SegmentationEngine`` (4 slots), every
    lane checked bitwise against a serial execute of the same plan.
(d) sharded (``--chips 4`` only): one slice at ``shards=4`` and at
    ``shards=1`` in both modes; the segmentations must be identical.

Every session runs with ``FallbackPolicy(enabled=False)``, a recorded
fallback event fails the phase, and a ``FusedKernelDowngradeWarning`` from
the fused kernels' VMEM guard is an error.  Reductions that ``reduce_by_key``
keeps on XLA (more than ``MAX_REDUCE_SEGMENTS`` segments) are reported in
the phase's ``downgrades``.  A phase's ``compile_s`` is the time JAX spent
in XLA compiles during it (persistent-cache reads included), so a second
run on a warm compile cache shows a lower figure.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
when every phase passed.  Without a TPU the script prints nothing on
stdout and exits 2; a failed phase makes it exit 1.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback
import warnings

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SIZE = 512                # the paper's synthetic slice edge
VOLUME_SLICES = 4
VOLUME_GRID = (64, 64)    # one region per 8x8 tile
FUSED_GRID = (16, 16)     # a bucket inside the fused kernel's one-hot guard
SERVE_REQUESTS = 8
SERVE_SLOTS = 4
SHARDS = 4

#: Bounds a phase fails below.  Accuracy: the synthetic phantom's noise
#: still leaves well above 0.9 for a working MRF.  Agreement: labels of
#: the TPU run against the f32 NumPy oracle and of the fused kernel
#: against the XLA composition; both are bitwise on CPU, and on the chip
#: only reduction order differs, which may flip a few ties.
MIN_ACCURACY = 0.9
MIN_AGREEMENT = 0.99

#: JAX's monitoring event around each XLA compile (or persistent-cache read).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class PhaseError(Exception):
    pass


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _config(**kw):
    from repro import api

    return api.ExecutionConfig(
        init="quantile", fallback=api.FallbackPolicy(enabled=False), **kw
    )


def _images(n: int, seed: int, size: int):
    import numpy as np

    from repro.core import synthetic

    vol = synthetic.make_synthetic_volume(seed=seed, n_slices=n, shape=(size, size))
    return [np.asarray(im) for im in vol.images], [
        np.asarray(gt) for gt in vol.ground_truth
    ]


def _check_no_fallback(*holders) -> None:
    for h in holders:
        if h.fallback_events:
            raise PhaseError(f"fallback events: {h.fallback_events}")


def _agreement(a, b) -> float:
    import numpy as np

    return float(np.mean(np.asarray(a) == np.asarray(b)))


def phase_volume(seed: int, size: int = SIZE, grid=VOLUME_GRID,
                 n_slices: int = VOLUME_SLICES) -> dict:
    import numpy as np

    from repro import api
    from repro.core import metrics
    from repro.core.pmrf import em as em_mod
    from repro.core.pmrf import reference

    images, truth = _images(n_slices, seed, size)
    sess = api.Segmenter(_config(mode="static", overseg_grid=grid))
    t0 = time.perf_counter()
    results, _ = sess.segment_stack(images, seed=seed)
    wall = time.perf_counter() - t0
    _check_no_fallback(sess)
    slices = []
    for i, r in enumerate(results):
        acc = metrics.evaluate(r.segmentation, truth[i]).accuracy
        slices.append({
            "slice": i, "accuracy": acc, "status": r.status,
            "em_iters": r.em_iters, "map_iters": r.map_iters,
            "plan_s": r.init_seconds, "optimize_s": r.optimize_seconds,
        })
    # The oracle replays slice 0's plan (planning is deterministic).
    problem = sess.plan(images[0]).problem
    labels0, mu0, sigma0 = em_mod.quantile_init(
        problem.graph.region_mean, problem.graph.n_regions, sess.config.n_labels
    )
    t1 = time.perf_counter()
    ref = reference.golden_em(
        problem.hoods, problem.model, np.asarray(labels0), np.asarray(mu0),
        np.asarray(sigma0), max_em_iters=sess.config.max_em_iters,
        max_map_iters=sess.config.max_map_iters,
    )
    nr = problem.graph.n_regions
    oracle = {
        "label_agreement": _agreement(results[0].region_labels, ref.labels[:nr]),
        "bitwise_labels": bool(np.array_equal(results[0].region_labels, ref.labels[:nr])),
        "em_iters": [results[0].em_iters, ref.em_iters],
        "map_iters": [results[0].map_iters, ref.map_iters],
        "mu_max_abs_diff": float(np.max(np.abs(results[0].mu - ref.mu))),
        "oracle_s": time.perf_counter() - t1,
    }
    out = {
        "phase": "a_volume", "size": size, "grid": list(grid),
        "bucket": list(sess.cache_keys[-1][:3]), "wall_s": wall,
        "slices": slices, "oracle": oracle,
    }
    bad = [
        s["slice"] for s in slices
        if s["accuracy"] < MIN_ACCURACY or s["status"] not in ("converged", "max_iters")
    ]
    if bad:
        raise PhaseError(f"slices {bad} below accuracy {MIN_ACCURACY} or unhealthy: {out}")
    if oracle["label_agreement"] < MIN_AGREEMENT:
        raise PhaseError(f"oracle agreement below {MIN_AGREEMENT}: {out}")
    return out


def phase_fused(seed: int, size: int = SIZE, grid=FUSED_GRID) -> dict:
    from repro import api
    from repro.core import metrics

    images, truth = _images(1, seed, size)
    sess_p = api.Segmenter(_config(mode="static-pallas", overseg_grid=grid))
    sess_s = api.Segmenter(_config(mode="static", overseg_grid=grid))
    plan = sess_p.plan(images[0])
    exe = sess_p.compile(plan)
    kernels = [
        line for line in exe.compiled.as_text().splitlines()
        if "tpu_custom_call" in line and "fused_em_tick" in line
    ]
    res_p = sess_p.execute(plan, seed=seed)
    res_s = sess_s.execute(plan, seed=seed)
    _check_no_fallback(sess_p, sess_s)
    out = {
        "phase": "b_fused", "size": size, "grid": list(grid),
        "bucket": list(plan.bucket), "fused_tick_calls_in_hlo": len(kernels),
        "label_agreement_vs_static": _agreement(res_p.region_labels, res_s.region_labels),
        "em_iters": [res_p.em_iters, res_s.em_iters],
        "accuracy": [metrics.evaluate(r.segmentation, truth[0]).accuracy
                     for r in (res_p, res_s)],
        "optimize_s": [res_p.optimize_seconds, res_s.optimize_seconds],
    }
    if not kernels:
        raise PhaseError(f"no fused_em_tick tpu_custom_call in the compiled program: {out}")
    if out["label_agreement_vs_static"] < MIN_AGREEMENT:
        raise PhaseError(f"static-pallas vs static agreement below {MIN_AGREEMENT}: {out}")
    return out


def phase_serving(seed: int, size: int = SIZE, grid=VOLUME_GRID,
                  n_requests: int = SERVE_REQUESTS, slots: int = SERVE_SLOTS) -> dict:
    import numpy as np

    from repro import api
    from repro.serving import SegmentationEngine

    images, _ = _images(n_requests, seed + 1, size)
    sess = api.Segmenter(
        _config(mode="static", overseg_grid=grid, capacity_bucket=4096)
    )
    plans = [sess.plan(img) for img in images]
    bucket = api.BucketKey(*(max(p.bucket[d] for p in plans) for d in range(3)))
    engine = SegmentationEngine(sess, max_batch=slots, tick_iters=8, bucket=bucket)
    t0 = time.perf_counter()
    for rid, p in enumerate(plans):
        engine.submit(p, rid=rid, seed=seed)
    completions = engine.run()
    wall = time.perf_counter() - t0
    mismatched = []
    for c in sorted(completions, key=lambda c: c.rid):
        want = sess.execute(plans[c.rid], seed=seed)
        if not (
            np.array_equal(c.result.region_labels, want.region_labels)
            and np.array_equal(c.result.segmentation, want.segmentation)
            and np.array_equal(c.result.mu, want.mu)
            and np.array_equal(c.result.sigma, want.sigma)
            and c.result.em_iters == want.em_iters
            and c.status == want.status
        ):
            mismatched.append(c.rid)
    _check_no_fallback(sess, engine)
    lat = [c.latency_s for c in completions]
    out = {
        "phase": "c_serving", "size": size, "grid": list(grid),
        "bucket": list(bucket), "requests": len(completions), "slots": slots,
        "wall_s": wall, "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_max_s": float(np.max(lat)),
        "statuses": sorted({c.status for c in completions}),
        "mismatched_vs_serial": mismatched,
        "ticks": engine.ticks,
    }
    if len(completions) != n_requests or mismatched:
        raise PhaseError(f"serving lanes disagree with serial execute: {out}")
    return out


def phase_sharded(seed: int, size: int = SIZE, shards: int = SHARDS) -> dict:
    import numpy as np

    from repro import api

    images, _ = _images(1, seed, size)
    modes = {}
    for mode, grid in (("static", VOLUME_GRID), ("static-pallas", FUSED_GRID)):
        segs, per = {}, {}
        for n in (1, shards):
            sess = api.Segmenter(_config(mode=mode, overseg_grid=grid, shards=n))
            plan = sess.plan(images[0])
            res = sess.execute(plan, seed=seed)
            _check_no_fallback(sess)
            segs[n] = res.segmentation
            per[str(n)] = {"em_iters": res.em_iters, "optimize_s": res.optimize_seconds}
        per["labels_match"] = bool(np.array_equal(segs[1], segs[shards]))
        per["grid"] = list(grid)
        modes[mode] = per
    out = {"phase": "d_sharded", "size": size, "shards": shards, "modes": modes}
    if not all(m["labels_match"] for m in modes.values()):
        raise PhaseError(f"sharded segmentation differs from one device: {out}")
    return out


def run_phase(fn, *args, **kw) -> dict:
    """Run one phase with fused-kernel downgrades as errors; record the
    reductions ``reduce_by_key`` kept on XLA and the phase's compile time."""
    import jax

    from repro.kernels.ops import BackendDowngradeWarning, FusedKernelDowngradeWarning

    compile_s = []

    def on_duration(event, seconds, **_):
        if event == COMPILE_EVENT:
            compile_s.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always", BackendDowngradeWarning)
            warnings.simplefilter("error", FusedKernelDowngradeWarning)
            t0 = time.perf_counter()
            out = fn(*args, **kw)
        out["phase_s"] = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    out["compile_s"] = sum(compile_s)
    out["compiles"] = len(compile_s)
    out["downgrades"] = sorted({
        str(w.message) for w in caught
        if issubclass(w.category, BackendDowngradeWarning)
    })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase (d) on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.xla_env import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    _emit({"device": device, "jax": jax.__version__, "compile_cache": cache_dir})

    phases = [(phase_sharded, {})] if args.chips == 4 else [
        (phase_volume, {}), (phase_fused, {}), (phase_serving, {}),
    ]
    failed = []
    for fn, kw in phases:
        try:
            _emit(run_phase(fn, args.seed, **kw))
        except Exception as e:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(fn.__name__)
            _emit({"phase": fn.__name__, "error": repr(e)[:4000]})
    if failed:
        _emit({"ok": False, "failed": failed, "device": device})
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
