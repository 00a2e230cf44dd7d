"""Segmentation serving driver: bring up the continuous-batching engine
(DESIGN.md §12) and drive a synthetic request stream through it, reporting
per-request latency percentiles and throughput.

``--check`` re-runs every request through the serial ``run_em`` executable
and exits non-zero on any label mismatch — the CI ``serve-smoke`` gate.
``--latency-gate N`` (with ``--check``) additionally times a warm serial
baseline and fails when the continuous healthy-lane **residence** p50
(admit -> retire, the part the engine controls; queue wait in this
batch-dump smoke is a pure function of oversubscription) exceeds ``N x``
the serial p50 — the §17 regression gate at smoke scale.  The pool
(every ladder size under ``--tick-iters auto``) and the serial
executable are compiled before the timed window, so the gate measures
serving, not compilation.

``--chaos`` activates the deterministic chaos harness (DESIGN.md §14):
``--poison-rate`` of the stream is assigned a fault class round-robin
(``nan_image`` — rejected at submit; ``bad_init`` / ``nan_data`` —
quarantined on-device as ``diverged``; ``never_converge`` — evicted).
With ``--check`` the gate also asserts every faulted request produced the
expected non-ok disposition and every healthy request still matches
serial ``run_em`` bitwise — the CI ``chaos-soak`` gate.

Usage::

    PYTHONPATH=src python -m repro.launch.serve \
        --requests 12 --shape 64 --grid 8 --max-batch 8 --tick-iters 8

(The LM generation driver this replaced lives at ``repro.launch.serve_lm``.)
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro import api
from repro.core import synthetic
from repro.serving import SegmentationEngine
from repro.serving.engine import DEFAULT_TICK_LADDER
from repro.testing import chaos as chaos_mod
from repro.xla_env import use_compile_cache

#: Fault classes --chaos cycles through (round-robin over the poisoned rids).
CHAOS_CYCLE = ("bad_init", "nan_image", "never_converge", "nan_data")


def assign_faults(n_requests: int, rate: float, seed: int) -> dict:
    """Deterministic rid -> fault-class map: ``round(n * rate)`` rids (at
    least 1 when rate > 0), spread by seeded choice, faults assigned
    round-robin so every class appears once the poison count allows."""
    if rate <= 0:
        return {}
    k = min(n_requests, max(1, round(n_requests * rate)))
    rng = np.random.default_rng(seed)
    rids = sorted(rng.choice(n_requests, size=k, replace=False).tolist())
    return {rid: CHAOS_CYCLE[i % len(CHAOS_CYCLE)] for i, rid in enumerate(rids)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--shape", type=int, default=64, help="square slice edge")
    ap.add_argument("--grid", type=int, default=8, help="oversegmentation grid edge")
    ap.add_argument("--max-batch", type=int, default=8, help="engine slot count")
    ap.add_argument("--tick-iters", default="8",
                    help="masked micro-steps per engine tick: an int, or "
                         "'auto' for the adaptive ladder policy (§17)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "xla", "pallas-tpu", "pallas-interpret"))
    ap.add_argument("--mode", default="static",
                    choices=("faithful", "static", "static-pallas"))
    ap.add_argument("--labels", type=int, default=2, metavar="K",
                    help="label count K; K>2 serves a K-phase synthetic "
                         "stream through the same pool (DESIGN.md §13)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-spread", type=float, default=0.0,
                    help="stagger request deadlines over this many seconds "
                         "(exercises deadline-ordered admission)")
    ap.add_argument("--check", action="store_true",
                    help="verify every lane result against serial run_em; "
                         "exit 1 on any label mismatch")
    ap.add_argument("--latency-gate", type=float, default=0.0, metavar="N",
                    help="with --check: fail when continuous healthy p50 "
                         "residence (admit->retire) exceeds N x warm "
                         "serial p50 (0 = off)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject deterministic faults into the stream "
                         "(DESIGN.md §14); with --check, also gate on "
                         "fault disposition")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--poison-rate", type=float, default=0.25,
                    help="fraction of requests assigned a fault under --chaos")
    ap.add_argument("--init", default="quantile", choices=("random", "quantile"),
                    help="EM parameter init (quantile converges reliably on "
                         "the synthetic phantoms)")
    args = ap.parse_args()
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if not 0.0 <= args.poison_rate <= 1.0:
        ap.error("--poison-rate must be in [0, 1]")
    if args.tick_iters == "auto":
        tick_iters = "auto"
    else:
        try:
            tick_iters = int(args.tick_iters)
        except ValueError:
            ap.error(f"--tick-iters must be an int or 'auto', got "
                     f"{args.tick_iters!r}")
    if args.latency_gate < 0:
        ap.error("--latency-gate must be >= 0")
    if args.latency_gate and not args.check:
        ap.error("--latency-gate requires --check")
    use_compile_cache()

    cfg = api.ExecutionConfig(
        backend=args.backend, mode=args.mode,
        overseg_grid=(args.grid, args.grid), capacity_bucket=4096,
        n_labels=args.labels, init=args.init,
    )
    sess = api.Segmenter(cfg)

    if args.labels > 2:
        vol = synthetic.make_kary_volume(
            seed=args.seed, n_slices=args.requests,
            shape=(args.shape, args.shape), n_phases=args.labels,
        )
    else:
        vol = synthetic.make_synthetic_volume(
            seed=args.seed, n_slices=args.requests, shape=(args.shape, args.shape)
        )
    imgs = [np.asarray(im) for im in vol.images]

    faults = (
        assign_faults(args.requests, args.poison_rate, args.chaos_seed)
        if args.chaos else {}
    )
    chaos_cfg = chaos_mod.ChaosConfig(
        seed=args.chaos_seed,
        nan_image_rids=tuple(r for r, f in faults.items() if f == "nan_image"),
        bad_init_rids=tuple(r for r, f in faults.items() if f == "bad_init"),
        nan_data_rids=tuple(r for r, f in faults.items() if f == "nan_data"),
        never_converge_rids=tuple(
            r for r, f in faults.items() if f == "never_converge"
        ),
    )
    # Healthy plans are prepared up front (plan time is not serving time);
    # nan_image rids get a poisoned raw image instead — plan() must reject.
    plans = {
        rid: sess.plan(img)
        for rid, img in enumerate(imgs)
        if faults.get(rid) != "nan_image"
    }

    # Fix the pool bucket up front and compile outside the timed window:
    # the serving numbers (and the --latency-gate) measure serving, not
    # compilation.  An adaptive engine warms its whole ladder here.
    bucket = None
    if plans:
        bucket = api.BucketKey(
            *(max(p.bucket[d] for p in plans.values()) for d in range(3))
        )
        ladder = DEFAULT_TICK_LADDER if tick_iters == "auto" else (tick_iters,)
        for t in ladder:
            sess.compile_ticked(bucket, batch=args.max_batch, tick_iters=t)
        if args.latency_gate:
            sess.compile(bucket)
            # Warm the per-plan padding and admission memos too: a cold
            # pad compile or lane-state build at admission time would
            # bill itself to whichever lanes happen to be resident.
            for p in plans.values():
                sess.lane_state(p, bucket=bucket, seed=args.seed)
            # One throwaway single-request drive compiles the engine's
            # module-level host jits (pool write/read/mark-done), which
            # are once-per-process costs, not serving costs.
            warm_eng = SegmentationEngine(
                sess, max_batch=args.max_batch, tick_iters=tick_iters,
                bucket=bucket,
            )
            warm_eng.submit(next(iter(plans.values())), rid=0, seed=args.seed)
            warm_eng.run()
    engine = SegmentationEngine(
        sess, max_batch=args.max_batch, tick_iters=tick_iters, bucket=bucket
    )
    rejected = []
    with chaos_mod.inject(chaos_cfg) as monkey:
        t0 = time.perf_counter()
        for rid in range(args.requests):
            deadline = (
                None if args.deadline_spread <= 0
                else args.deadline_spread * rid / max(args.requests - 1, 1)
            )
            if faults.get(rid) == "nan_image":
                try:
                    engine.submit(
                        monkey.poison_image(imgs[rid], rid),
                        rid=rid, seed=args.seed, deadline_s=deadline,
                    )
                except api.ServingError:
                    rejected.append(rid)
                continue
            engine.submit(plans[rid], rid=rid, seed=args.seed, deadline_s=deadline)
        completions = engine.run()
        wall = time.perf_counter() - t0

    by_rid = {c.rid: c for c in completions}
    healthy = [c for c in completions if c.rid not in faults]
    lat = np.array([c.latency_s for c in completions])
    queue = np.array([c.queue_s for c in completions])
    residence = np.array([c.residence_s for c in completions])
    report = {
        "requests": len(completions),
        "labels": args.labels,
        "max_batch": args.max_batch,
        "tick_policy": "auto" if tick_iters == "auto" else "fixed",
        "bucket": list(engine.bucket),
        "wall_s": round(wall, 3),
        "throughput_rps": round(len(completions) / wall, 2),
        "healthy_rps": round(len(healthy) / wall, 2),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 4),
        "latency_p95_s": round(float(np.percentile(lat, 95)), 4),
        # Honest accounting (§17): latency = queue (waiting for a slot)
        # + residence (resident in a lane), reported separately.
        "queue_p50_s": round(float(np.percentile(queue, 50)), 4),
        "queue_p95_s": round(float(np.percentile(queue, 95)), 4),
        "residence_p50_s": round(float(np.percentile(residence, 50)), 4),
        "residence_p95_s": round(float(np.percentile(residence, 95)), 4),
        "mean_em_iters": round(
            float(np.mean([c.result.em_iters for c in completions])), 2
        ),
        **engine.stats(),
    }
    if args.chaos:
        report["chaos"] = {
            "seed": args.chaos_seed,
            "poison_rate": args.poison_rate,
            "faults": {str(r): f for r, f in sorted(faults.items())},
            "rejected_rids": rejected,
            "statuses": {str(c.rid): c.status for c in completions if not c.ok},
            "injections": len(monkey.events),
        }

    failures = []
    if args.check:
        # Healthy lanes must match serial run_em bitwise — chaos or not
        # (serial reference runs OUTSIDE the chaos context).  The same
        # executes double as the warm serial baseline for --latency-gate.
        lat_serial = []
        if args.latency_gate and healthy:
            sess.execute(plans[healthy[0].rid], seed=args.seed)  # warm memos
        for c in sorted(healthy, key=lambda c: c.rid):
            t1 = time.perf_counter()
            want = sess.execute(plans[c.rid], seed=args.seed)
            lat_serial.append(time.perf_counter() - t1)
            if not (
                np.array_equal(c.result.region_labels, want.region_labels)
                and np.array_equal(c.result.segmentation, want.segmentation)
                and np.array_equal(c.result.mu, want.mu)
                and np.array_equal(c.result.sigma, want.sigma)
                and c.result.em_iters == want.em_iters
                and c.status == want.status
            ):
                failures.append(f"rid {c.rid}: lane diverged from serial run_em")
        # Faulted requests must have the expected disposition.
        for rid, fault in sorted(faults.items()):
            if fault == "nan_image":
                if rid not in rejected:
                    failures.append(f"rid {rid}: poisoned image was not rejected")
            elif rid not in by_rid:
                failures.append(f"rid {rid}: faulted request never completed")
            elif fault in ("bad_init", "nan_data"):
                if by_rid[rid].status != "diverged":
                    failures.append(
                        f"rid {rid}: {fault} lane status "
                        f"{by_rid[rid].status!r}, want 'diverged'"
                    )
            elif fault == "never_converge":
                if by_rid[rid].status != "evicted":
                    failures.append(
                        f"rid {rid}: never_converge lane status "
                        f"{by_rid[rid].status!r}, want 'evicted'"
                    )
        # §17 latency gate: continuous healthy residence p50 vs the warm
        # serial p50 just measured.  Residence (admit -> retire) is what
        # the engine controls — tick granularity, early exit, per-tick
        # host overhead; queue wait in this batch-dump smoke is set by
        # the requests/slots ratio, which would gate the workload, not
        # the engine.
        if args.latency_gate and healthy:
            serial_p50 = float(np.percentile(lat_serial, 50))
            res_p50 = float(np.percentile([c.residence_s for c in healthy], 50))
            report["serial_p50_s"] = round(serial_p50, 4)
            report["latency_gate_x"] = round(res_p50 / max(serial_p50, 1e-9), 2)
            if res_p50 > args.latency_gate * serial_p50:
                failures.append(
                    f"latency gate: continuous healthy residence p50 "
                    f"{res_p50:.4f}s > {args.latency_gate}x serial p50 "
                    f"{serial_p50:.4f}s"
                )
        report["check"] = "ok" if not failures else failures

    print(json.dumps(report))
    if failures:
        print("serve --check FAILED:", *failures, sep="\n  ", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
