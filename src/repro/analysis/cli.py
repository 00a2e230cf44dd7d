"""``python -m repro.analysis`` — audit every executable the session
layer can produce (DESIGN.md §15).

Three passes, one deterministic report:

1. **jaxpr audit** — traces every registered driver at the audit
   bucket for every (mode, backend, K) combo and runs the JX detectors
   (``jaxpr_lint``).  Tracing is shape-independent in cost, so this
   audits the production-scale avals the serving engine compiles
   without compiling anything.
2. **Pallas kernel check** — builds the jaxpr of each registered kernel
   and runs the PL detectors (``pallas_check``).
3. **budget sentinel** — compiles one tiny end-to-end scenario and
   measures the declared phase budgets (``budget.BUDGETS``) live;
   overshoot becomes a ``BG001`` finding.

Exit status: 0 when every finding is suppressed (and, under
``--check``, the checked-in ``ANALYSIS.json`` baseline matches);
1 otherwise.  ``--write`` regenerates the baseline — the CI drift gate
runs ``--write`` and requires an empty git diff, exactly like the
golden fixtures.
"""

from __future__ import annotations

import argparse
import sys
from typing import Dict, List, Optional, Tuple

from . import budget as budget_mod
from . import registry
from .findings import Finding, apply_suppressions, report_to_json
from .jaxpr_lint import LintThresholds, lint_jaxpr
from .pallas_check import check_jaxpr_kernels

__all__ = ["run_audit", "main"]


def _trace_driver(spec: registry.DriverSpec, mode: str, backend: str, k: int):
    """Trace one driver at the audit bucket; returns its ClosedJaxpr."""
    from repro.api import session as sess
    from repro.api.config import ExecutionConfig
    from repro.core.pmrf import em as em_mod

    bucket = sess.BucketKey(*registry.AUDIT_BUCKET)
    cfg = ExecutionConfig(mode=mode, backend=backend, n_labels=k)
    emc = cfg.em_config(backend=backend)
    if spec.ticked:
        hoods, model, *_ = sess._abstract_inputs(
            bucket, registry.AUDIT_BATCH, 1, k
        )
        state = sess._abstract_tick_state(bucket, registry.AUDIT_BATCH, k)
        traced = em_mod.run_em_ticked.trace(
            hoods, model, state, emc, registry.AUDIT_TICK_ITERS
        )
    else:
        batch = registry.AUDIT_BATCH if spec.batched else None
        abstract = sess._abstract_inputs(bucket, batch, 1, k)
        fn = em_mod.run_em_batched if spec.batched else em_mod.run_em
        traced = fn.trace(*abstract, emc)
    return traced.jaxpr


def _audit_jaxprs(log) -> Tuple[List[Finding], List[Dict]]:
    findings: List[Finding] = []
    entries: List[Dict] = []
    for mode in registry.MODES:
        for backend in registry.BACKENDS:
            for k in registry.KS:
                for spec in registry.DRIVERS:
                    site = f"{spec.name}[{mode}/{backend}/K={k}]"
                    log(f"  trace {site}")
                    closed = _trace_driver(spec, mode, backend, k)
                    b = registry.loop_budget(spec.name, mode, backend)
                    th = LintThresholds(
                        scatter_budget=None if b is None else b["scatter"],
                        gather_budget=None if b is None else b["gather"],
                    )
                    fs, census = lint_jaxpr(closed, site, thresholds=th)
                    findings.extend(fs)
                    entries.append(
                        {
                            "driver": spec.name,
                            "mode": mode,
                            "backend": backend,
                            "k": k,
                            "loop_census": census.as_dict(),
                            "loop_budget": b,
                            "findings": [f.as_dict() for f in sorted(fs)],
                        }
                    )
    return findings, entries


def _kernel_jaxprs():
    """(site, ClosedJaxpr) for every registered Pallas kernel, built at
    representative shapes.  Import-heavy, so local."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core.pmrf import em as em_mod
    from repro.kernels import (
        em_tick as et,
        flash_attention as fa,
        map_step as ms,
        mrf_energy as me,
        segment_reduce as sr,
    )

    f32 = jnp.float32
    H, S, R = 65536, 4096, 4096
    e = jax.ShapeDtypeStruct((H,), f32)
    i = jax.ShapeDtypeStruct((H,), jnp.int32)
    v = jax.ShapeDtypeStruct((H,), jnp.bool_)

    out = []
    for op in ("add", "min"):
        fn = functools.partial(
            sr.segment_reduce_pallas, num_segments=S, op=op, interpret=True
        )
        out.append((f"segment_reduce[{op}]", jax.make_jaxpr(fn)(e, i)))

    mu2 = jax.ShapeDtypeStruct((2,), f32)
    fn = functools.partial(me.mrf_min_energy_pallas, beta=0.75, interpret=True)
    out.append(("mrf_min_energy", jax.make_jaxpr(fn)(e, e, e, e, e, mu2, mu2)))

    for k in registry.KS:
        muk = jax.ShapeDtypeStruct((k,), f32)
        cnt = jax.ShapeDtypeStruct((k, H), f32)
        fn = functools.partial(
            ms.fused_map_step_pallas,
            beta=0.75, n_hoods=S, n_vertices=R, interpret=True,
        )
        out.append(
            (
                f"fused_map_step[K={k}]",
                jax.make_jaxpr(fn)(e, e, cnt, e, e, v, i, i, muk, muk),
            )
        )

    hist = jax.ShapeDtypeStruct((em_mod.WINDOW + 1, S), f32)
    r = jax.ShapeDtypeStruct((R,), f32)
    for k in registry.KS:
        muk = jax.ShapeDtypeStruct((k,), f32)
        fn = functools.partial(
            et.fused_em_tick_pallas,
            beta=0.75, n_hoods=S, n_vertices=R, precision="f32",
            conv_tol=1e-4, interpret=True,
        )
        out.append(
            (
                f"fused_em_tick[K={k}]",
                jax.make_jaxpr(fn)(e, e, e, e, e, i, i, r, r, hist, muk, muk),
            )
        )

    q = jax.ShapeDtypeStruct((1, 4, 512, 64), f32)
    fn = functools.partial(fa.flash_attention_pallas, interpret=True)
    out.append(("flash_attention", jax.make_jaxpr(fn)(q, q, q)))
    return out


def _audit_kernels(log) -> Tuple[List[Finding], List[Dict]]:
    findings: List[Finding] = []
    entries: List[Dict] = []
    for site, closed in _kernel_jaxprs():
        log(f"  check kernel {site}")
        for rep in check_jaxpr_kernels(closed, site):
            findings.extend(rep.findings)
            entries.append(rep.as_dict())
    return findings, entries


def _audit_budgets(log) -> Tuple[List[Finding], Dict]:
    """Live smoke: one tiny compile/execute scenario per declared phase."""
    import numpy as np
    from repro.api import Segmenter
    from repro.api.config import ExecutionConfig
    from repro.core.synthetic import make_synthetic_volume

    log("  budget sentinel smoke (tiny compile/execute)")
    findings: List[Finding] = []
    measured: Dict[str, int] = {}
    cfg = ExecutionConfig(
        mode="static", backend="xla", max_em_iters=2, max_map_iters=2
    )
    seg = Segmenter(cfg)
    image = np.asarray(
        make_synthetic_volume(seed=0, n_slices=1, shape=(32, 32)).images[0]
    )
    plan = seg.plan(image)

    def run(phase, fn):
        b = budget_mod.budget_for(phase)
        before = budget_mod.LEDGER.total(b.section)
        try:
            with budget_mod.expect(phase):
                fn()
        except budget_mod.BudgetExceeded as exc:
            findings.append(
                Finding("BG001", "error", f"budget:{phase}", str(exc))
            )
        measured[phase] = budget_mod.LEDGER.total(b.section) - before

    run("cold_compile", lambda: seg.execute(plan))
    run("warm_execute", lambda: seg.execute(plan))

    exe = seg.compile_ticked(plan.bucket, batch=2, tick_iters=2)
    pools = seg.ticked_pool(plan.bucket, batch=2)
    run("warm_tick", lambda: exe(*pools))

    declared = [
        {"phase": b.phase, "section": b.section,
         "max_delta": b.max_delta, "note": b.note}
        for b in budget_mod.BUDGETS
    ]
    return findings, {"declared": declared, "measured": measured}


def run_audit(verbose: bool = True) -> Dict:
    """Run all three passes; returns the (deterministic) report dict."""
    log = (lambda s: print(s, file=sys.stderr)) if verbose else (lambda s: None)

    log("jaxpr audit:")
    jx_findings, jx_entries = _audit_jaxprs(log)
    log("pallas kernel check:")
    pl_findings, pl_entries = _audit_kernels(log)
    budget_mod.reset_all()  # the audit's own traces don't count
    bg_findings, budgets = _audit_budgets(log)

    all_findings = sorted(jx_findings + pl_findings + bg_findings)
    all_findings, stale = apply_suppressions(all_findings, registry.SUPPRESSIONS)
    unsuppressed = [f for f in all_findings if not f.suppressed]

    return {
        "version": 1,
        "matrix": {
            "bucket": list(registry.AUDIT_BUCKET),
            "batch": registry.AUDIT_BATCH,
            "tick_iters": registry.AUDIT_TICK_ITERS,
            "modes": list(registry.MODES),
            "backends": list(registry.BACKENDS),
            "ks": list(registry.KS),
        },
        "jaxpr": jx_entries,
        "kernels": pl_entries,
        "budgets": budgets,
        "suppressions": [
            {"code": s.code, "site_pattern": s.site_pattern, "reason": s.reason}
            for s in registry.SUPPRESSIONS
        ],
        "stale_suppressions": [
            {"code": s.code, "site_pattern": s.site_pattern} for s in stale
        ],
        "summary": {
            "findings": len(all_findings),
            "suppressed": len(all_findings) - len(unsuppressed),
            "unsuppressed": len(unsuppressed),
        },
        "unsuppressed_findings": [f.as_dict() for f in unsuppressed],
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static auditor for compiled executables "
        "(jaxpr lint + Pallas checks + budget sentinel)",
    )
    p.add_argument(
        "--check", action="store_true",
        help="fail on any unsuppressed finding, stale suppression, or "
        "drift from the checked-in baseline",
    )
    p.add_argument(
        "--write", action="store_true",
        help="regenerate the ANALYSIS.json baseline",
    )
    p.add_argument("--out", default="ANALYSIS.json", help="baseline path")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)

    report = run_audit(verbose=not args.quiet)
    text = report_to_json(report)
    s = report["summary"]
    print(
        f"analysis: {s['findings']} finding(s), {s['suppressed']} suppressed, "
        f"{s['unsuppressed']} unsuppressed"
    )
    for f in report["unsuppressed_findings"]:
        print(f"  {f['severity'].upper()} {f['code']} {f['site']}: {f['message']}")
    for s_ in report["stale_suppressions"]:
        print(f"  STALE suppression {s_['code']} {s_['site_pattern']}")

    rc = 0
    if args.write:
        with open(args.out, "w") as fh:
            fh.write(text)
        print(f"wrote {args.out}")
    if args.check:
        if report["unsuppressed_findings"] or report["stale_suppressions"]:
            rc = 1
        try:
            with open(args.out) as fh:
                baseline = fh.read()
        except OSError:
            print(f"missing baseline {args.out} (run with --write)")
            rc = 1
        else:
            if baseline != text:
                print(f"baseline {args.out} drifted (regenerate with --write)")
                rc = 1
    if rc == 0 and not report["unsuppressed_findings"]:
        print("analysis: OK")
    return rc
