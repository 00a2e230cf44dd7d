"""ShapeDtypeStruct input stand-ins + step builders for every
(architecture x shape) cell — shared by the dry-run, the drivers, and the
roofline model (``launch/roofline.py``).

``input_specs(cfg, shape)`` returns allocation-free stand-ins for every
model input of the cell's step kind:

* ``train``   — {tokens, labels, mask} (+ frames / vision_embeds stubs)
* ``prefill`` — {tokens} (+ stubs); the step is ``prefill`` itself
* ``decode``  — {tokens: (B, 1)} plus the *cache* pytree for seq_len
                already-generated positions (one new token against a full
                KV/state cache — the assignment's decode semantics)

``build_step(cfg, shape, mesh)`` assembles the jit'd step with
in/out_shardings pinned so ``.lower(*specs)`` works from structs alone.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeSpec
from repro.models import layers as L
from repro.models.registry import get_api
from repro.models.transformer import ParallelRuntime
from repro.parallel import sharding as SH
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (
    TrainStepConfig,
    make_train_step,
    state_shape,
    state_specs,
)

Array = jax.Array
SDS = jax.ShapeDtypeStruct


def _struct(shape, dtype) -> SDS:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# ---------------------------------------------------------------------------
# input structs
# ---------------------------------------------------------------------------


def batch_structs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, SDS]:
    """Model-input stand-ins for a train/prefill batch."""
    b, s = shape.global_batch, shape.seq_len
    out: Dict[str, SDS] = {"tokens": _struct((b, s), jnp.int32)}
    if shape.kind == "train":
        out["labels"] = _struct((b, s), jnp.int32)
        out["mask"] = _struct((b, s), jnp.float32)
    if cfg.family == "encdec":
        out["frames"] = _struct((b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        out["vision_embeds"] = _struct(
            (b, cfg.vision_patches, cfg.d_model), jnp.float32
        )
    return out


def decode_structs(
    cfg: ModelConfig, shape: ShapeSpec
) -> Tuple[Dict[str, SDS], Any]:
    """(tokens, cache) stand-ins for a decode step at the cell's seq_len."""
    b, s = shape.global_batch, shape.seq_len
    api = get_api(cfg)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, b, s))
    return {"tokens": _struct((b, 1), jnp.int32)}, cache


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    """All input stand-ins for the cell, keyed by role."""
    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        tokens, cache = decode_structs(cfg, shape)
        return {"batch": tokens, "cache": cache}
    return {"batch": batch_structs(cfg, shape)}


# ---------------------------------------------------------------------------
# runtimes / shardings per step kind
# ---------------------------------------------------------------------------


def _dp_spec(mesh: Mesh, n: int):
    dp = SH.dp_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    return dp if (dp and n % size == 0) else None


def serve_runtime(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh) -> ParallelRuntime:
    """Decode runtime: sequence-parallel cache attention when the cache's
    seq dim divides the model axis (sp_attention flash combine)."""
    m = mesh.shape.get("model", 1)
    has_kv_seq = cfg.family in ("dense", "moe", "mla_moe", "vlm", "encdec", "hybrid")
    seq_ok = has_kv_seq and shape.seq_len % m == 0 and m > 1
    return ParallelRuntime(
        mesh=mesh,
        dp_axes=SH.dp_axes(mesh),
        tp_axis="model" if "model" in mesh.axis_names else "",
        seq_axis="model" if seq_ok else "",
        decode_batch_spec=_dp_spec(mesh, shape.global_batch),
    )


@dataclass
class CellStep:
    """A lowered-compilable step for one (arch x shape x mesh) cell."""

    fn: Callable                      # jit'd step
    args: Tuple[Any, ...]             # ShapeDtypeStructs to .lower(*args)
    kind: str                         # train | prefill | decode
    n_params: int
    n_active_params: int


def build_step(
    cfg: ModelConfig,
    shape_name: str,
    mesh: Mesh,
    *,
    ts_cfg: Optional[TrainStepConfig] = None,
) -> CellStep:
    shape = SHAPES[shape_name]
    api = get_api(cfg)
    ts_cfg = ts_cfg or TrainStepConfig()

    if shape.kind == "train":
        batch = batch_structs(cfg, shape)
        sspecs = state_specs(cfg, ts_cfg.optimizer, mesh)
        step = make_train_step(
            cfg, mesh, ts_cfg, state_partition=sspecs, batch_shape=batch
        )
        sshapes = state_shape(cfg, ts_cfg.optimizer)
        return CellStep(
            fn=step,
            args=(sshapes, batch),
            kind="train",
            n_params=cfg.n_params(),
            n_active_params=cfg.active_params(),
        )

    # inference: parameter shardings only (no optimizer state)
    pshapes = jax.eval_shape(lambda k: api.init(k, cfg), jax.random.PRNGKey(0))
    pspecs = SH.param_specs(pshapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    if shape.kind == "prefill":
        batch = batch_structs(cfg, shape)
        bsh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            SH.batch_specs(batch, mesh, global_batch=shape.global_batch),
            is_leaf=lambda x: isinstance(x, P),
        )
        import os
        rt = ParallelRuntime(
            mesh=mesh,
            dp_axes=SH.dp_axes(mesh),
            tp_axis="model" if "model" in mesh.axis_names else "",
            pin_attn_seq=os.environ.get("REPRO_PIN_ATTN", "1") == "1",
        )

        def prefill_step(params, b):
            return api.prefill(params, b, cfg, rt, max_seq=shape.seq_len)

        fn = jax.jit(prefill_step, in_shardings=(psh, bsh))
        return CellStep(
            fn=fn,
            args=(pshapes, batch),
            kind="prefill",
            n_params=cfg.n_params(),
            n_active_params=cfg.active_params(),
        )

    # decode
    tokens, cache = decode_structs(cfg, shape)
    rt = serve_runtime(cfg, shape, mesh)
    cspecs = SH.cache_specs(cache, mesh, cfg, batch=shape.global_batch)
    csh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), cspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    tsh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        SH.batch_specs(tokens, mesh, global_batch=shape.global_batch),
        is_leaf=lambda x: isinstance(x, P),
    )

    def decode_step(params, c, tok):
        return api.decode_step(params, c, {"tokens": tok}, cfg, rt)

    fn = jax.jit(
        decode_step,
        in_shardings=(psh, csh, tsh["tokens"]),
        out_shardings=(None, csh),
        donate_argnums=(1,),
    )
    return CellStep(
        fn=fn,
        args=(pshapes, cache, tokens["tokens"]),
        kind="decode",
        n_params=cfg.n_params(),
        n_active_params=cfg.active_params(),
    )


def runnable_cells(cfg: ModelConfig) -> Tuple[str, ...]:
    """Shape names this arch runs (assignment skips recorded in cfg)."""
    return tuple(s for s in SHAPES if s not in cfg.skip_shapes)
