"""Subprocess entry point for multi-device tests.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
parent test via env, NOT globally — smoke tests must see 1 device).
Exits nonzero on any assertion failure.
"""

import os
import sys

# Must happen before jax import in the subprocess (the parent sets env).
assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "runner must be launched with XLA_FLAGS=--xla_force_host_platform_device_count=N"
)

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def check_sharded_dpps():
    from repro.core import dpp_sharded

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("data",))
    n = 64
    x = jnp.arange(n, dtype=jnp.float32) * 0.5 - 7.0
    seg = jnp.asarray(np.random.RandomState(0).randint(0, 5, size=n), jnp.int32)

    scan_fn = shard_map(
        lambda v: dpp_sharded.global_scan(v, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    )
    np.testing.assert_allclose(np.asarray(scan_fn(x)), np.cumsum(np.asarray(x)), rtol=1e-5)

    scan_ex = shard_map(
        lambda v: dpp_sharded.global_scan(v, "data", exclusive=True),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    )
    want = np.cumsum(np.asarray(x)) - np.asarray(x)
    np.testing.assert_allclose(np.asarray(scan_ex(x)), want, rtol=1e-5)

    red = shard_map(
        lambda v: dpp_sharded.global_reduce(v, "data", "add"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
    np.testing.assert_allclose(float(red(x)), float(jnp.sum(x)), rtol=1e-5)

    rbk = shard_map(
        lambda s, v: dpp_sharded.global_reduce_by_key(s, v, 5, "data", "add"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
    )
    got = np.asarray(rbk(seg, x))
    want = np.zeros(5, np.float32)
    np.add.at(want, np.asarray(seg), np.asarray(x))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    print("sharded DPPs OK")


def check_distributed_em():
    """The unified collective-parametrized driver (DESIGN.md §11): sharded
    results bit-identical to single-device for ALL THREE execution modes."""
    from repro.core import synthetic
    from repro.core.pmrf import EMConfig, initialize, run_em
    from repro.core.pmrf import em as em_mod
    from repro.core.pmrf.distributed import distributed_em

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("data",))

    vol = synthetic.make_synthetic_volume(seed=0, n_slices=1, shape=(64, 64))
    img = np.asarray(vol.images[0])
    problem = initialize(img, overseg_grid=(8, 8))
    labels0, mu0, sigma0 = em_mod.init_params(jax.random.PRNGKey(0), problem.graph.n_regions)

    for mode in ("faithful", "static", "static-pallas"):
        config = EMConfig(mode=mode)
        ref = run_em(problem.hoods, problem.model, labels0, mu0, sigma0, config)
        dist = distributed_em(
            problem.hoods, problem.model, labels0, mu0, sigma0, mesh, "data", config
        )
        np.testing.assert_array_equal(np.asarray(ref.labels), np.asarray(dist.labels))
        np.testing.assert_allclose(np.asarray(ref.mu), np.asarray(dist.mu), rtol=1e-5)
        np.testing.assert_allclose(
            float(ref.total_energy), float(dist.total_energy), rtol=1e-4
        )
        assert int(ref.em_iters) == int(dist.em_iters), mode
        print("  %s: bit-identical labels, em_iters=%d" % (mode, int(ref.em_iters)))
    print("distributed EM OK (all modes)")


def check_session_sharded():
    """Session-layer sharding: ExecutionConfig(shards=8) compiles/caches a
    sharded executable (shards in the key), matches the unsharded result,
    and warm hits perform zero traces.

    Deliberately twins tests/test_sharded_em.py's in-process variant: that
    one only *runs* when the process already has 8 devices (the
    tier1-multidevice CI job), so this subprocess check is what guards the
    sharded session path in the default single-device tier-1 suite.
    """
    from repro import api
    from repro.core import synthetic
    from repro.core.pmrf import em as em_mod

    vol = synthetic.make_synthetic_volume(seed=3, n_slices=1, shape=(44, 44))
    img = np.asarray(vol.images[0])
    base = api.Segmenter(api.ExecutionConfig(overseg_grid=(6, 6)))
    sharded = api.Segmenter(api.ExecutionConfig(overseg_grid=(6, 6), shards=8))

    ref = base.segment(img, seed=0)
    plan = sharded.plan(img)
    got = sharded.execute(plan, seed=0)
    np.testing.assert_array_equal(ref.segmentation, got.segmentation)
    np.testing.assert_array_equal(ref.region_labels, got.region_labels)
    assert ref.em_iters == got.em_iters

    assert sharded.cache_keys[0].shards == 8
    assert base.cache_keys[0].shards == 1
    assert sharded.cache_keys[0] != base.cache_keys[0]
    before = dict(em_mod.TRACE_COUNTS)
    assert before["run_em_sharded"] >= 1
    sharded.execute(plan, seed=0)
    assert em_mod.TRACE_COUNTS == before, "warm sharded execute traced"
    assert sharded.stats.hits == 1
    print("session sharded OK (shards=8 key, zero-trace warm hit)")


def _mini_shape(name, seq, batch, kind):
    from repro.configs.base import SHAPES, ShapeSpec

    spec = ShapeSpec(name, seq, batch, kind)
    SHAPES[name] = spec
    return spec


def check_mini_dryrun():
    """build_step lowers + compiles for every family on an 8-device
    (data=2, model=4) mesh with reduced configs — the dry-run machinery
    end-to-end at test scale, including the loop-aware roofline terms."""
    import dataclasses

    from repro.configs import ARCHS, get_config
    from repro.launch import hlo_cost
    from repro.launch.specs import build_step

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    _mini_shape("mini_train", 64, 4, "train")
    _mini_shape("mini_decode", 64, 4, "decode")

    for arch in ("qwen2-1.5b", "qwen3-moe-235b-a22b", "deepseek-v2-lite-16b",
                 "mamba2-130m", "zamba2-2.7b", "whisper-large-v3",
                 "llava-next-34b"):
        cfg = get_config(arch).reduced()
        cfg = dataclasses.replace(cfg, logit_chunk=32, attn_chunk=32)
        for shape in ("mini_train", "mini_decode"):
            cell = build_step(cfg, shape, mesh)
            with mesh:
                compiled = cell.fn.lower(*cell.args).compile()
            totals = hlo_cost.analyze(compiled.as_text())
            assert totals.flops > 0, (arch, shape)
            assert totals.hbm_bytes > 0, (arch, shape)
            print(f"  mini-dryrun ok: {arch} {shape} "
                  f"flops={totals.flops:.2e} coll={totals.coll_total_bytes:.2e}")
    print("mini dryrun OK")


def check_grad_codec():
    """Cross-pod codec'd gradient step on a (pod=2,data=2,model=2) mesh:
    int8-stochastic and bf16 codecs converge to the uncompressed gradient
    (int8 within quantization noise; bf16 within bf16 eps)."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.specs import batch_structs
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import (
        TrainStepConfig,
        make_sharded_train_state,
        make_train_step,
        state_specs,
    )
    from repro.configs.base import SHAPES

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    _mini_shape("mini_train8", 32, 8, "train")
    cfg = get_config("qwen2-1.5b").reduced()
    cfg = dataclasses.replace(cfg, logit_chunk=32, attn_chunk=32)

    losses = {}
    gnorms = {}
    for codec in ("none", "bf16", "int8"):
        ts_cfg = TrainStepConfig(
            optimizer=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10),
            grad_codec=codec,
        )
        state, sspecs = make_sharded_train_state(cfg, mesh, ts_cfg)
        batch_shape = jax.eval_shape(
            lambda: {
                "tokens": jnp.zeros((8, 32), jnp.int32),
                "labels": jnp.zeros((8, 32), jnp.int32),
                "mask": jnp.ones((8, 32), jnp.float32),
            }
        )
        step = make_train_step(
            cfg, mesh, ts_cfg, state_partition=sspecs, batch_shape=batch_shape
        )
        rng = np.random.RandomState(0)
        batch = {
            "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "mask": jnp.ones((8, 32), jnp.float32),
        }
        with mesh:
            state2, metrics = step(state, batch)
        losses[codec] = float(metrics["loss"])
        gnorms[codec] = float(metrics["grad_norm"])
        print(f"  codec={codec}: loss={losses[codec]:.4f} gnorm={gnorms[codec]:.4f}")

    assert abs(losses["bf16"] - losses["none"]) < 1e-3
    assert abs(losses["int8"] - losses["none"]) < 1e-3
    assert abs(gnorms["bf16"] - gnorms["none"]) / gnorms["none"] < 0.02
    assert abs(gnorms["int8"] - gnorms["none"]) / gnorms["none"] < 0.05
    print("grad codec OK")


def check_elastic_remesh():
    """Checkpoint saved under one mesh restores onto a different mesh
    (and a different device count) with identical values."""
    import tempfile

    from repro.training import checkpoint as CK
    from jax.sharding import NamedSharding

    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    mesh_b = jax.make_mesh((2, 2), ("data", "model"))  # "lost" half the fleet

    state = {
        "w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.arange(8, dtype=jnp.bfloat16),
        "step": jnp.asarray(7, jnp.int32),
    }
    specs = {"w": P("data", "model"), "b": P("model"), "step": P()}
    sharded = {
        k: jax.device_put(v, NamedSharding(mesh_a, specs[k]))
        for k, v in state.items()
    }
    with tempfile.TemporaryDirectory() as d:
        CK.save_checkpoint(d, 7, sharded, specs=specs, mesh=mesh_a)
        step, restored, _ = CK.restore_checkpoint(d, state, mesh=mesh_b)
        assert step == 7
        for k in state:
            np.testing.assert_array_equal(
                np.asarray(restored[k], np.float32), np.asarray(state[k], np.float32)
            )
            shard_mesh = restored[k].sharding.mesh
            assert shard_mesh.devices.size == mesh_b.devices.size
    print("elastic re-mesh OK")


def check_sp_decode():
    """Sequence-parallel cached decode (flash combine) matches the
    single-device decode path bit-for-bit (fp32 tolerance)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models.transformer import ParallelRuntime

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), attn_chunk=32
    )
    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.RandomState(0)
    b, s_max, t = 2, 64, 17
    p = A.gqa_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.randn(b, 1, cfg.d_model), jnp.float32)
    kc = jnp.asarray(rng.randn(b, cfg.n_kv_heads, s_max, cfg.head_dim), jnp.float32)
    vc = jnp.asarray(rng.randn(b, cfg.n_kv_heads, s_max, cfg.head_dim), jnp.float32)
    # zero out unwritten cache positions > t for exactness
    mask = (np.arange(s_max) <= t)[None, None, :, None]
    kc = kc * mask
    vc = vc * mask

    out_ref, kc_ref, vc_ref = A.gqa_decode(p, x, cfg, kc, vc, jnp.asarray(t))
    rt = ParallelRuntime(mesh=mesh, dp_axes=(), tp_axis="model",
                         seq_axis="model", decode_batch_spec=None)
    with mesh:
        out_sp, kc_sp, vc_sp = jax.jit(
            lambda pp, xx, kk, vv, tt: A.gqa_decode(pp, xx, cfg, kk, vv, tt, rt=rt)
        )(p, x, kc, vc, jnp.asarray(t))
    np.testing.assert_allclose(
        np.asarray(out_ref), np.asarray(out_sp), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(kc_ref), np.asarray(kc_sp), rtol=1e-6, atol=1e-6
    )
    print("sp decode OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    assert jax.device_count() >= 8, jax.devices()
    if which in ("all", "dpps"):
        check_sharded_dpps()
    if which in ("all", "em"):
        check_distributed_em()
    if which in ("all", "session"):
        check_session_sharded()
    if which in ("all", "minidryrun"):
        check_mini_dryrun()
    if which in ("all", "codec"):
        check_grad_codec()
    if which in ("all", "remesh"):
        check_elastic_remesh()
    if which in ("all", "spdecode"):
        check_sp_decode()
    print("OK")
