"""Decoder-only transformer assembly (dense / MoE / MLA / VLM families).

Design invariants:

* **scan-over-layers** — per-layer parameters are stacked on a leading L
  axis and the stack is traversed with ``lax.scan``, keeping HLO size O(1)
  in depth (94-layer qwen3-moe lowers as fast as 2-layer smoke configs) —
  mandatory for 512-way SPMD compile times (DESIGN.md §6).
* **remat** — the scanned layer body is wrapped in ``jax.checkpoint`` with
  a configurable policy (cfg.remat_policy).
* **pure functions** — init is eval_shape-able; no global state.  The
  parallel runtime (mesh + axis names) is threaded explicitly.
* activations are bf16 (cfg.compute_dtype); the loss and softmax run fp32.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M

Array = jax.Array
Params = Dict[str, Any]


class ParallelRuntime(NamedTuple):
    """Mesh context threaded through model calls (None = single device)."""

    mesh: Any = None
    dp_axes: Tuple[str, ...] = ()   # batch-sharding axes, e.g. ("pod","data")
    tp_axis: str = ""               # tensor/expert-parallel axis ("model")
    seq_axis: str = ""              # cache-sequence sharding axis for decode
                                    # (sp_attention flash combine); "" = off
    decode_batch_spec: Any = None   # P entry for the decode batch dim
    pin_attn_seq: bool = True       # pin q/accumulators to sequence sharding
                                    # through the flash KV scan (§Perf B1)

    @property
    def active(self) -> bool:
        return self.mesh is not None


def shard_act(x: Array, rt: Optional[ParallelRuntime], *axes) -> Array:
    """with_sharding_constraint helper; axes name mesh axes per dim (None ok)."""
    if rt is None or not rt.active:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(rt.mesh, P(*axes))
    )


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": L.dense_init(ks[0], d, f, dtype),
        "w_up": L.dense_init(ks[1], d, f, dtype),
        "w_down": L.dense_init(ks[2], f, d, dtype),
    }


def mlp_apply(p: Params, x: Array) -> Array:
    h = jax.nn.silu((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (h * (x @ p["w_up"])) @ p["w_down"]


def layer_init(
    key, cfg: ModelConfig, dtype, *, attn: str, ffn: str, d_ff: int = 0
) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"ln1": jnp.ones((cfg.d_model,), dtype), "ln2": jnp.ones((cfg.d_model,), dtype)}
    if attn == "gqa":
        p["attn"] = A.gqa_init(k1, cfg, dtype)
    elif attn == "mla":
        p["attn"] = A.mla_init(k1, cfg, dtype)
    else:
        raise ValueError(attn)
    if ffn == "mlp":
        p["mlp"] = mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff, dtype)
    elif ffn == "moe":
        p["moe"] = M.moe_init(k2, cfg, dtype)
    else:
        raise ValueError(ffn)
    return p


def _layer_kinds(cfg: ModelConfig) -> Tuple[str, str]:
    attn = "mla" if cfg.family == "mla_moe" else "gqa"
    ffn = "moe" if cfg.family in ("moe", "mla_moe") else "mlp"
    return attn, ffn


def decoder_init(key, cfg: ModelConfig) -> Params:
    dtype = L.dtype_of(cfg.param_dtype)
    attn, ffn = _layer_kinds(cfg)
    n_scan = cfg.n_layers - (1 if cfg.dense_d_ff_first else 0)

    k_emb, k_layers, k_first, k_out = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, n_scan)
    layers = jax.vmap(
        lambda k: layer_init(k, cfg, dtype, attn=attn, ffn=ffn)
    )(layer_keys)

    params: Params = {
        "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.dense_d_ff_first:
        params["first_layer"] = layer_init(
            k_first, cfg, dtype, attn=attn, ffn="mlp", d_ff=cfg.dense_d_ff_first
        )
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _attn_apply(p, x, cfg: ModelConfig, rt, *, causal=True):
    if cfg.family == "mla_moe":
        return A.mla_attn(p["attn"], x, cfg, causal=causal, rt=rt)
    return A.gqa_attn(p["attn"], x, cfg, causal=causal, rt=rt)


def _ffn_apply(p, x, cfg: ModelConfig, rt: Optional[ParallelRuntime]):
    if "moe" in p:
        if rt is not None and rt.active:
            mesh = rt.mesh
            dp = rt.dp_axes
            tp = rt.tp_axis
            moe_p = p["moe"]

            def local_fn(mp, xl):
                return M.moe_ffn(mp, xl, cfg, axis=tp)

            in_specs = (
                {
                    "router": P(),
                    "w_gate": P(tp), "w_up": P(tp), "w_down": P(tp),
                    **({"shared": P()} if "shared" in moe_p else {}),
                },
                P(dp, None, None),
            )
            return jax.shard_map(
                local_fn, mesh=mesh, in_specs=in_specs,
                out_specs=P(dp, None, None), check_vma=False,
            )(moe_p, x)
        return M.moe_ffn(p["moe"], x, cfg, axis=None)
    return mlp_apply(p["mlp"], x)


def _layer_body(p, x, cfg: ModelConfig, rt, *, causal=True):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + _attn_apply(p, h, cfg, rt, causal=causal)
    x = shard_act(x, rt, rt.dp_axes if rt else None, None, None)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn_apply(p, h, cfg, rt)
    return x


_REMAT_POLICIES = {
    "none": None,
    "dots": "dots_saveable",
    # saves only weight matmuls (no batch dims in the dot): flash-attention
    # score/PV dots are NOT stashed — see EXPERIMENTS.md §Perf A2/C1,
    # where "dots" was measured stashing the (L, chunks, B, S, chunk)
    # attention internals (674 GiB/chip on zamba2/qwen3 train_4k).
    "dots_nb": "checkpoint_dots_with_no_batch_dims",
    "full": "nothing_saveable",
}


def _remat(fn, cfg: ModelConfig):
    policy_name = _REMAT_POLICIES[cfg.remat_policy]
    if policy_name is None:
        return fn
    policy = getattr(jax.checkpoint_policies, policy_name)
    return jax.checkpoint(fn, policy=policy)


def decoder_hidden(
    params: Params,
    tokens: Array,
    cfg: ModelConfig,
    rt: Optional[ParallelRuntime] = None,
    *,
    vision_embeds: Optional[Array] = None,
) -> Array:
    """Token ids (B, S) -> final hidden states (B, S, D)."""
    cdt = L.dtype_of(cfg.compute_dtype)
    x = params["embed"][tokens].astype(cdt)

    if cfg.family == "vlm":
        assert vision_embeds is not None, "vlm needs patch embeddings"
        npatch = vision_embeds.shape[1]
        # patches occupy the prompt prefix (anyres tiles are pre-flattened
        # by the stub frontend; see input_specs)
        x = jnp.concatenate(
            [vision_embeds.astype(cdt), x[:, npatch:]], axis=1
        )

    x = shard_act(x, rt, rt.dp_axes if rt else None, None, None)

    if cfg.dense_d_ff_first:
        x = _layer_body(params["first_layer"], x, cfg, rt)

    body = _remat(
        lambda xx, lp: (_layer_body(lp, xx, cfg, rt), None), cfg
    )
    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


def logits_fn(params: Params, cfg: ModelConfig, hidden: Array) -> Array:
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return hidden @ w.astype(hidden.dtype)


def lm_loss(
    params: Params,
    batch: Dict[str, Array],
    cfg: ModelConfig,
    rt: Optional[ParallelRuntime] = None,
) -> Array:
    """Next-token cross entropy with chunked vocab projection."""
    hidden = decoder_hidden(
        params, batch["tokens"], cfg, rt,
        vision_embeds=batch.get("vision_embeds"),
    )
    return L.chunked_softmax_xent(
        lambda h: logits_fn(params, cfg, h),
        hidden,
        batch["labels"],
        batch["mask"].astype(jnp.float32),
        min(cfg.logit_chunk, hidden.shape[1]),
    )


# ---------------------------------------------------------------------------
# serving: prefill + decode with caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Array]:
    cdt = L.dtype_of(cfg.compute_dtype)
    n_scan = cfg.n_layers - (1 if cfg.dense_d_ff_first else 0)
    if cfg.family == "mla_moe":
        cache = {
            "ckv": jnp.zeros((n_scan, batch, max_seq, cfg.mla_kv_lora_rank), cdt),
            "krope": jnp.zeros((n_scan, batch, 1, max_seq, cfg.mla_rope_head_dim), cdt),
            "t": jnp.zeros((), jnp.int32),
        }
        if cfg.dense_d_ff_first:
            cache["first_ckv"] = jnp.zeros((batch, max_seq, cfg.mla_kv_lora_rank), cdt)
            cache["first_krope"] = jnp.zeros((batch, 1, max_seq, cfg.mla_rope_head_dim), cdt)
        return cache
    return {
        "k": jnp.zeros((n_scan, batch, cfg.n_kv_heads, max_seq, cfg.head_dim), cdt),
        "v": jnp.zeros((n_scan, batch, cfg.n_kv_heads, max_seq, cfg.head_dim), cdt),
        "t": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Params,
    cache: Dict[str, Array],
    tokens: Array,
    cfg: ModelConfig,
    rt: Optional[ParallelRuntime] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """One decode step.  tokens: (B, 1) -> logits (B, 1, V), updated cache."""
    cdt = L.dtype_of(cfg.compute_dtype)
    x = params["embed"][tokens].astype(cdt)
    t = cache["t"]
    new_cache = dict(cache)

    if cfg.dense_d_ff_first:
        p0 = params["first_layer"]
        h = L.rms_norm(x, p0["ln1"], cfg.norm_eps)
        if cfg.family == "mla_moe":
            att, ckv, krope = A.mla_decode(
                p0["attn"], h, cfg, cache["first_ckv"], cache["first_krope"], t
            )
            new_cache["first_ckv"], new_cache["first_krope"] = ckv, krope
        else:
            raise AssertionError("dense-first only used by mla_moe family")
        x = x + att
        h = L.rms_norm(x, p0["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p0["mlp"], h)

    def body(carry, xs):
        xx = carry
        if cfg.family == "mla_moe":
            lp, ckv, krope = xs
            h = L.rms_norm(xx, lp["ln1"], cfg.norm_eps)
            att, ckv, krope = A.mla_decode(lp["attn"], h, cfg, ckv, krope, t, rt=rt)
            xx = xx + att
            h = L.rms_norm(xx, lp["ln2"], cfg.norm_eps)
            xx = xx + _ffn_apply(lp, h, cfg, rt)
            return xx, (ckv, krope)
        lp, kc, vc = xs
        h = L.rms_norm(xx, lp["ln1"], cfg.norm_eps)
        att, kc, vc = A.gqa_decode(lp["attn"], h, cfg, kc, vc, t, rt=rt)
        xx = xx + att
        h = L.rms_norm(xx, lp["ln2"], cfg.norm_eps)
        xx = xx + _ffn_apply(lp, h, cfg, rt)
        return xx, (kc, vc)

    if cfg.family == "mla_moe":
        x, (ckv, krope) = jax.lax.scan(body, x, (params["layers"], cache["ckv"], cache["krope"]))
        new_cache["ckv"], new_cache["krope"] = ckv, krope
    else:
        x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = k, v

    new_cache["t"] = t + 1
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, x)
    return logits.astype(jnp.float32), new_cache


def prefill(
    params: Params,
    tokens: Array,
    cfg: ModelConfig,
    rt: Optional[ParallelRuntime] = None,
    *,
    max_seq: Optional[int] = None,
    vision_embeds: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """Process a full prompt, returning last-position logits + filled cache.

    The cache is populated by recomputing K/V per layer outside the decode
    loop (prefill attention itself uses the flash path).
    """
    b, s = tokens.shape
    max_seq = max_seq or s
    cdt = L.dtype_of(cfg.compute_dtype)
    cache = init_cache(cfg, b, max_seq)
    x = params["embed"][tokens].astype(cdt)
    if cfg.family == "vlm" and vision_embeds is not None:
        npatch = vision_embeds.shape[1]
        x = jnp.concatenate([vision_embeds.astype(cdt), x[:, npatch:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def fill(lp, xx, cache_slices):
        h = L.rms_norm(xx, lp["ln1"], cfg.norm_eps)
        if cfg.family == "mla_moe":
            q_nope, q_rope, c_kv, k_rope = A._mla_qkv(lp["attn"], h, cfg, positions)
            ckv, krope = cache_slices
            ckv = ckv.at[:, :s].set(c_kv)
            krope = krope.at[:, :, :s].set(k_rope)
            att = A._mla_attend(
                lp["attn"], q_nope, q_rope, c_kv, k_rope, cfg, causal=True
            )
            new_slices = (ckv, krope)
        else:
            q, k, v = A.gqa_project_qkv(lp["attn"], h, cfg, positions)
            kc, vc = cache_slices
            kc = kc.at[:, :, :s].set(k)
            vc = vc.at[:, :, :s].set(v)
            out = A.attention_dispatch(q, k, v, causal=True, chunk=cfg.attn_chunk, rt=rt)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
            att = out @ lp["attn"]["wo"]
            new_slices = (kc, vc)
        xx = xx + att
        h = L.rms_norm(xx, lp["ln2"], cfg.norm_eps)
        xx = xx + _ffn_apply(lp, h, cfg, rt)
        return xx, new_slices

    if cfg.dense_d_ff_first:
        p0 = params["first_layer"]
        x, (ckv0, kr0) = fill(p0, x, (cache["first_ckv"], cache["first_krope"]))
        cache["first_ckv"], cache["first_krope"] = ckv0, kr0

    if cfg.family == "mla_moe":
        def body(xx, xs):
            lp, ckv, krope = xs
            xx, (ckv, krope) = fill(lp, xx, (ckv, krope))
            return xx, (ckv, krope)
        x, (ckv, krope) = jax.lax.scan(body, x, (params["layers"], cache["ckv"], cache["krope"]))
        cache["ckv"], cache["krope"] = ckv, krope
    else:
        def body(xx, xs):
            lp, kc, vc = xs
            xx, (kc, vc) = fill(lp, xx, (kc, vc))
            return xx, (kc, vc)
        x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        cache["k"], cache["v"] = k, v

    cache["t"] = jnp.asarray(s, jnp.int32)
    x = L.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, x)
    return logits.astype(jnp.float32), cache
