"""Dry-run machinery tests: the loop-aware HLO cost model (the basis of
EXPERIMENTS.md §Roofline) validated against programs with known costs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost
from repro.launch.roofline import Roofline, analytic_flash_traffic, model_flops_for


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_scale_with_trip_count():
    """cost_analysis counts a while body once; hlo_cost multiplies by the
    trip count — the bug this module exists to fix."""
    M = 64

    def scanned(a, b):
        def body(x, _):
            return jnp.tanh(x @ b), None
        y, _ = jax.lax.scan(body, a, None, length=10)
        return y

    s = jax.ShapeDtypeStruct((M, M), jnp.float32)
    c = _compile(scanned, s, s)
    t = hlo_cost.analyze(c.as_text())
    want_dots = 10 * 2 * M * M * M
    assert want_dots <= t.flops <= want_dots * 1.1, t.flops
    # XLA's own counter misses the loop:
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns one dict per program
        ca = ca[0]
    xla = ca.get("flops", 0)
    assert xla < t.flops / 5


def test_single_dot_flops_exact():
    M, N, K = 32, 48, 64
    c = _compile(
        lambda a, b: a @ b,
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32),
    )
    t = hlo_cost.analyze(c.as_text())
    assert t.flops == pytest.approx(2 * M * N * K, rel=0.01)


def test_nested_scan_multiplies():
    def nested(a):
        def outer(x, _):
            def inner(y, _):
                return y * 2.0, None
            y, _ = jax.lax.scan(inner, x, None, length=5)
            return y, None
        x, _ = jax.lax.scan(outer, a, None, length=3)
        return x

    c = _compile(nested, jax.ShapeDtypeStruct((128,), jnp.float32))
    t = hlo_cost.analyze(c.as_text())
    # 3*5 multiplies of 128 elements (+ loop counters)
    assert 15 * 128 <= t.flops <= 15 * 128 * 1.5


def test_dus_charged_at_slice_size():
    """dynamic-update-slice into a big buffer must charge ~2x the slice,
    not the buffer."""
    BIG, SLICE = 4096, 32

    def f(buf, upd, i):
        def body(carry, j):
            b, u = carry
            b = jax.lax.dynamic_update_slice(b, u, (j * 0,))
            return (b, u), None
        (buf, _), _ = jax.lax.scan(body, (buf, upd), jnp.arange(8))
        return buf

    c = _compile(
        f,
        jax.ShapeDtypeStruct((BIG,), jnp.float32),
        jax.ShapeDtypeStruct((SLICE,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    t = hlo_cost.analyze(c.as_text())
    # 8 iterations x ~2x32 floats — allow generous slack for loop plumbing,
    # but the full 4096 buffer per iteration (8 x 16 KiB = 131 KiB) must
    # NOT be charged.
    assert t.hbm_bytes < 60_000, t.hbm_bytes


def test_roofline_terms_and_bound():
    r = Roofline(
        flops_per_chip=197e12 * 0.5,        # 0.5s compute
        hbm_bytes_per_chip=819e9 * 0.1,     # 0.1s memory
        coll_bytes_per_chip=50e9 * 0.2,     # 0.2s collective
        n_chips=256,
        model_flops=197e12 * 0.5 * 256 * 0.8,
    )
    assert r.bound == "compute"
    assert r.step_s == pytest.approx(0.5)
    assert r.useful_flops_ratio == pytest.approx(0.8)
    assert r.mfu == pytest.approx(0.8)


def test_model_flops_conventions():
    assert model_flops_for("train", 10, 10, 100) == 6 * 10 * 100
    assert model_flops_for("prefill", 10, 10, 100) == 2 * 10 * 100
    # MoE counts active params
    assert model_flops_for("train", 100, 20, 10) == 6 * 20 * 10


def test_analytic_flash_traffic_families():
    from repro.configs import get_config
    from repro.configs.base import SHAPES

    mesh_shape = {"data": 16, "model": 16}
    shape = SHAPES["train_4k"]
    dense = analytic_flash_traffic(get_config("qwen2-1.5b"), shape, mesh_shape, "train")
    assert dense > 0
    # attention-free, but the fused-SSD kernel has its own stream traffic
    ssm = analytic_flash_traffic(get_config("mamba2-130m"), shape, mesh_shape, "train")
    assert ssm > 0
    # hybrid = SSD stream + the (n_layers/6) shared-attn applications
    hyb = analytic_flash_traffic(get_config("zamba2-2.7b"), shape, mesh_shape, "train")
    assert hyb > 0


def test_collective_parse_with_loops():
    """Collectives inside scanned bodies are multiplied by trip count."""
    mesh_txt = """
HloModule test, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  %ar = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  ROOT %t = (s32[], f32[8]{0}) tuple(%ni, %ar)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %p)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
    t = hlo_cost.analyze(mesh_txt)
    assert t.coll_count.get("all-reduce") == 7
    assert t.coll_bytes.get("all-reduce") == 7 * 8 * 4


def test_artifacts_exist_and_complete():
    """Every (arch x shape) cell has a single-pod artifact: ok or a
    documented skip."""
    import json
    from pathlib import Path

    from repro.configs import ARCHS
    from repro.configs.base import SHAPES

    art = Path(__file__).parent.parent / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated yet")
    missing, bad = [], []
    for arch in ARCHS:
        for shape in SHAPES:
            p = art / f"{arch}__{shape}__pod16x16.json"
            if not p.exists():
                missing.append((arch, shape))
                continue
            d = json.loads(p.read_text())
            if d["status"] == "ok":
                r = d["roofline"]
                if not (r["compute_s"] > 0 and r["memory_s"] > 0):
                    bad.append((arch, shape))
            elif not d["status"].startswith("skip"):
                bad.append((arch, shape))
    assert not missing, f"missing cells: {missing}"
    assert not bad, f"bad cells: {bad}"
