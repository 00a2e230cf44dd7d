"""Mixture-of-Experts FFN with DPP-based dispatch.

This is the paper's technique surfacing inside the LM stack (DESIGN.md §4):
token->expert dispatch is exactly the DPP-PMRF replicate/reduce pattern —

  Map        router logits + top-k gate
  SortByKey  (expert, token) pairs so each expert's tokens are contiguous
  Scan       rank-within-expert (capacity positions) via the expand idiom
  Scatter    tokens into the (E, C, D) dispatch buffer (capacity drop)
  Gather     expert outputs back to token order
  ReduceByKey(weighted combine over the top-k replicas of each token)

Expert parallelism: experts are sharded over the ``model`` mesh axis.  The
sharded path runs the dispatch *locally per model shard* on replicated
tokens (each shard owns E/n experts and simply ignores tokens routed
elsewhere), then one psum combines expert outputs — the same collective
shape as a Megatron row-parallel matmul, with zero all-to-alls
(DESIGN.md §6).  Inside shard_map, every step is static-shape.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dpp
from repro.models import layers as L

Array = jax.Array


def moe_init(key, cfg: ModelConfig, dtype) -> Dict[str, Array]:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], d, e, jnp.float32),  # router in fp32
        "w_gate": _expert_init(ks[1], e, d, f, dtype),
        "w_up": _expert_init(ks[2], e, d, f, dtype),
        "w_down": _expert_init(ks[3], e, f, d, dtype),
    }
    if cfg.moe_shared_experts:
        fs = cfg.moe_d_ff * cfg.moe_shared_experts
        p["shared"] = {
            "w_gate": L.dense_init(ks[4], d, fs, dtype),
            "w_up": L.dense_init(jax.random.fold_in(ks[4], 1), d, fs, dtype),
            "w_down": L.dense_init(jax.random.fold_in(ks[4], 2), fs, d, dtype),
        }
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out)) * scale).astype(dtype)


def _capacity(n_tokens: int, cfg: ModelConfig, n_experts_pool: int) -> int:
    c = int(n_tokens * cfg.moe_top_k * cfg.moe_capacity_factor / n_experts_pool)
    return max(8, -(-c // 8) * 8)  # multiple of 8 lanes


def moe_ffn_local(
    p: Dict[str, Array],
    x2d: Array,
    cfg: ModelConfig,
    *,
    expert_offset: int = 0,
    n_local_experts: Optional[int] = None,
) -> Array:
    """Dispatch + expert FFN over a local expert slice.

    x2d: (T, D) tokens.  ``p['w_*']`` hold only the local experts
    (E_loc, ...); the router is global (E columns).  Returns the combined
    output for tokens hitting local experts (zeros elsewhere) — callers
    psum across the expert-sharding axis.
    """
    t, d = x2d.shape
    e_global = cfg.moe_num_experts
    e_loc = n_local_experts if n_local_experts is not None else p["w_gate"].shape[0]
    k = cfg.moe_top_k
    cap = _capacity(t, cfg, e_global)

    # --- Map: router + top-k gates (fp32 for stable softmax) ---------------
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    gates, experts = jax.lax.top_k(logits, k)               # (T, k)
    gates = jax.nn.softmax(gates, axis=-1)

    flat_expert = experts.reshape(-1)                        # (T*k,)
    flat_gate = gates.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    # keep only local experts; re-base ids
    local_e = flat_expert - expert_offset
    is_local = (local_e >= 0) & (local_e < e_loc)
    local_e = jnp.where(is_local, local_e, e_loc)            # sentinel bucket

    # --- SortByKey: group (expert, token) pairs by expert ------------------
    # Only integer lanes ride through the sort (this jaxlib's sort JVP is
    # broken, and integer-only sorts need no JVP); differentiable values
    # (gates, activations) are gathered afterwards through the permutation.
    key = dpp.compound_key(local_e, flat_token, t)
    lanes = jnp.arange(key.shape[0], dtype=jnp.int32)
    s_key, s_lane = dpp.sort_by_key(key, lanes)
    s_token = jnp.take(flat_token, s_lane)
    s_gate = jnp.take(flat_gate, s_lane)
    s_expert = (s_key // t).astype(jnp.int32)

    # --- Scan: rank within expert (capacity position) ----------------------
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (s_expert[1:] != s_expert[:-1]).astype(jnp.int32)]
    )
    lane = jnp.arange(s_expert.shape[0], dtype=jnp.int32)
    seg_first_lane = jax.lax.associative_scan(
        jnp.maximum, jnp.where(seg_start == 1, lane, -1)
    )
    rank = lane - seg_first_lane

    keep = (s_expert < e_loc) & (rank < cap)

    # --- Scatter: tokens into the (E_loc * C, D) dispatch buffer -----------
    slot = s_expert * cap + rank
    slot = jnp.where(keep, slot, e_loc * cap)                # dropped lanes
    x_sorted = jnp.take(x2d, s_token, axis=0)
    buf = jnp.zeros((e_loc * cap + 1, d), x2d.dtype).at[slot].set(x_sorted)
    buf = buf[:-1].reshape(e_loc, cap, d)

    # --- expert FFN (SwiGLU), batched einsum over local experts ------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(u.dtype) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])         # (E_loc, C, D)

    # --- Gather + weighted combine back to token order ---------------------
    out_flat = out.reshape(e_loc * cap, d)
    gathered = jnp.take(out_flat, jnp.minimum(slot, e_loc * cap - 1), axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0)
    contrib = gathered.astype(jnp.float32) * s_gate[:, None]
    combined = jnp.zeros((t, d), jnp.float32).at[s_token].add(contrib)
    return combined.astype(x2d.dtype)


def moe_ffn(
    p: Dict[str, Array],
    x: Array,
    cfg: ModelConfig,
    *,
    axis: Optional[str] = None,
) -> Array:
    """MoE FFN over (B, S, D) activations.

    ``axis`` names the mesh axis experts are sharded over; it must be
    passed when called inside shard_map.  Outside shard_map (single
    device / smoke tests) the full expert set runs locally.
    """
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    if axis is None:
        out = moe_ffn_local(p, x2d, cfg)
    else:
        idx = jax.lax.axis_index(axis)
        n_shards = jax.lax.axis_size(axis)
        e_loc = cfg.moe_num_experts // n_shards
        out = moe_ffn_local(
            p, x2d, cfg, expert_offset=idx * e_loc, n_local_experts=e_loc
        )
        out = jax.lax.psum(out, axis)
    out = out.reshape(b, s, d)

    if cfg.moe_shared_experts and "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu((x @ sp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
        out = out + (h * (x @ sp["w_up"])) @ sp["w_down"]
    return out


def router_aux_loss(p, x2d: Array, cfg: ModelConfig) -> Array:
    """Load-balancing auxiliary loss (Switch-style): E * sum(f_e * p_e)."""
    logits = x2d.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(logits, cfg.moe_top_k)
    onehot = jax.nn.one_hot(experts, cfg.moe_num_experts, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    return cfg.moe_num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
