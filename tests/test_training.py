"""Training substrate tests: optimizer math, schedule, data determinism,
checkpoint atomicity/integrity, and crash-recovery exactness."""

import dataclasses
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.training import checkpoint as CK
from repro.training import data as data_mod
from repro.training.fault import run_training
from repro.training.optimizer import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    global_norm,
    lr_schedule,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_matches_manual_formula():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      grad_clip=1e9, warmup_steps=0, total_steps=10**9,
                      min_lr_frac=1.0)
    params = {"w": jnp.asarray([[1.0, -2.0]])}
    grads = {"w": jnp.asarray([[0.5, 0.25]])}
    state = adamw_init(params, cfg)
    new_params, state, _ = adamw_update(grads, state, params, cfg)

    g = np.asarray([[0.5, 0.25]])
    m = 0.1 * g
    v = 0.01 * g * g
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    want = np.asarray([[1.0, -2.0]]) - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_params["w"]), want, rtol=1e-6)


def test_weight_decay_skips_1d_params():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, grad_clip=1e9,
                      warmup_steps=0, total_steps=10**9, min_lr_frac=1.0)
    params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    grads = jax.tree.map(jnp.zeros_like, params)
    state = adamw_init(params, cfg)
    new_params, _, _ = adamw_update(grads, state, params, cfg)
    # zero grads: only decay moves weights; biases must not move
    assert float(jnp.max(jnp.abs(new_params["b"] - 1.0))) == 0.0
    assert float(jnp.max(jnp.abs(new_params["w"] - 1.0))) > 0.0


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_frac=0.1)
    lrs = [float(lr_schedule(jnp.asarray(s), cfg)) for s in range(0, 120, 5)]
    assert lrs[0] == 0.0
    assert abs(lrs[2] - 1.0) < 1e-6          # end of warmup
    assert lrs[-1] == pytest.approx(0.1, rel=1e-3)  # cosine floor
    assert all(a >= b - 1e-9 for a, b in zip(lrs[2:], lrs[3:]))  # monotone decay


@settings(max_examples=20, deadline=None)
# No subnormals: XLA:CPU flushes them to zero, which numpy does not.
@given(st.lists(st.floats(-100, 100, allow_nan=False, allow_subnormal=False,
                          width=32),
                min_size=1, max_size=16))
def test_clip_by_global_norm_bound(xs):
    g = {"x": jnp.asarray(xs, jnp.float32)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    got = float(global_norm(clipped))
    assert got <= 1.0 + 1e-4
    if float(norm) <= 1.0:
        np.testing.assert_allclose(np.asarray(clipped["x"]), np.asarray(xs),
                                   rtol=1e-6)


def test_training_reduces_loss_quickly():
    """A tiny LM on the copy-task stream must drop loss within 30 steps."""
    from repro.configs import get_config
    from repro.training.train_step import (
        TrainStepConfig, make_sharded_train_state, make_train_step,
    )

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(), logit_chunk=32, attn_chunk=32
    )
    ts = TrainStepConfig(optimizer=AdamWConfig(
        lr=3e-3, warmup_steps=5, total_steps=40, use_master_fp32=False))
    state, _ = make_sharded_train_state(cfg, None, ts)
    step = make_train_step(cfg, None, ts)
    dcfg = data_mod.DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                               global_batch=8)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in data_mod.make_batch(dcfg, i).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_microbatch_accumulation_matches_full_batch():
    from repro.configs import get_config
    from repro.training.train_step import (
        TrainStepConfig, make_sharded_train_state, make_train_step,
    )

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(),
        logit_chunk=32, attn_chunk=32,
        param_dtype="float32", compute_dtype="float32",
    )
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    dcfg = data_mod.DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                               global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in data_mod.make_batch(dcfg, 0).items()}

    outs = {}
    for n_micro in (1, 4):
        ts = TrainStepConfig(optimizer=opt, microbatches=n_micro)
        state, _ = make_sharded_train_state(cfg, None, ts)
        step = make_train_step(cfg, None, ts)
        new_state, metrics = step(state, batch)
        outs[n_micro] = (float(metrics["loss"]),
                         np.asarray(new_state["params"]["final_norm"]))
    # microbatched loss is the mean of per-microbatch means — equal here
    # because every microbatch has the same token count
    assert outs[1][0] == pytest.approx(outs[4][0], rel=1e-4)
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_batches_deterministic_and_distinct():
    cfg = data_mod.DataConfig(vocab_size=1000, seq_len=64, global_batch=4)
    b1 = data_mod.make_batch(cfg, 7)
    b2 = data_mod.make_batch(cfg, 7)
    b3 = data_mod.make_batch(cfg, 8)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (4, 64)
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_data_host_sharding_partitions_batch():
    cfg = data_mod.DataConfig(vocab_size=1000, seq_len=16, global_batch=8)
    full = data_mod.make_batch(cfg, 3)
    parts = [
        data_mod.make_batch(cfg, 3, host_index=i, host_count=4) for i in range(4)
    ]
    assert all(p["tokens"].shape == (2, 16) for p in parts)
    # host shards are mutually distinct streams (independent rngs)
    assert len({p["tokens"].tobytes() for p in parts}) == 4


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------


def _toy_state():
    return {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": jnp.arange(4, dtype=jnp.bfloat16),
        "opt": {"step": jnp.asarray(3, jnp.int32)},
    }


def test_checkpoint_roundtrip_and_retention():
    state = _toy_state()
    with tempfile.TemporaryDirectory() as d:
        for s in (10, 20, 30, 40):
            CK.save_checkpoint(d, s, state, keep_last=2)
        assert CK.latest_step(d) == 40
        # retention pruned the old ones
        steps = sorted(int(p.name[5:]) for p in Path(d).glob("step_*")
                       if p.is_dir())
        assert steps == [30, 40]
        step, restored, _ = CK.restore_checkpoint(d, state)
        assert step == 40
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
        assert restored["b"].dtype == jnp.bfloat16


def test_checkpoint_detects_corruption():
    state = _toy_state()
    with tempfile.TemporaryDirectory() as d:
        CK.save_checkpoint(d, 5, state)
        victim = next((Path(d) / "step_00000005").glob("w.npy"))
        raw = bytearray(victim.read_bytes())
        raw[-1] ^= 0xFF
        victim.write_bytes(bytes(raw))
        with pytest.raises(IOError, match="hash mismatch"):
            CK.restore_checkpoint(d, state)


def test_checkpoint_ignores_uncommitted():
    state = _toy_state()
    with tempfile.TemporaryDirectory() as d:
        CK.save_checkpoint(d, 5, state)
        # simulate a mid-save preemption at step 9: dir exists, no marker
        (Path(d) / "step_00000009").mkdir()
        assert CK.latest_step(d) == 5


def test_crash_recovery_resumes_exactly():
    """Kill training mid-run (injected), restart, and verify the final
    state equals an uninterrupted run — checkpoint/restart exactness."""

    def make_setup():
        params = {"w": jnp.zeros((4,), jnp.float32)}

        def step_fn(state, batch):
            new = {"w": state["w"] + batch["x"]}
            return new, {"loss": jnp.sum(new["w"])}

        def make_batch(i):
            return {"x": jnp.full((4,), float(i + 1), jnp.float32)}

        return params, step_fn, make_batch

    # uninterrupted reference
    params, step_fn, make_batch = make_setup()
    ref = params
    for i in range(10):
        ref, _ = step_fn(ref, make_batch(i))

    with tempfile.TemporaryDirectory() as d:
        params, step_fn, make_batch = make_setup()
        with pytest.raises(RuntimeError, match="injected failure"):
            run_training(
                step_fn=step_fn, state=params, make_batch=make_batch,
                num_steps=10, ckpt_dir=d, ckpt_every=2, log_every=0,
                crash_at_step=7,
            )
        # restart from the last committed checkpoint (step 6)
        params2, step_fn, make_batch = make_setup()
        report = run_training(
            step_fn=step_fn, state=params2, make_batch=make_batch,
            num_steps=10, ckpt_dir=d, ckpt_every=2, log_every=0,
        )
        # the step-6 save is async; the injected crash may land before its
        # commit — either way restart must resume from a *committed* step
        assert report.resumed_from in (4, 6)
        _, final, _ = CK.restore_checkpoint(d, params2)
        np.testing.assert_allclose(np.asarray(final["w"]), np.asarray(ref["w"]))
