"""Sequence-parallel cached-decode attention (flash combine across shards).

At 32k-500k context the KV cache dominates device memory, so the cache's
sequence dim is sharded over the ``model`` axis (parallel/sharding.py).
Decode attention then needs a cross-shard softmax: each shard computes an
online-softmax partial (m, l, acc) over its local KV slice and the partials
are merged with the standard flash rescaling identity

    m* = pmax(m),   l* = psum(l . e^{m-m*}),   acc* = psum(acc . e^{m-m*})

— one tiny all-reduce per decode step instead of all-gathering gigabytes
of cache.  The new token's K/V are written by the owning shard only
(position t falls in exactly one shard's slice).

Implemented as shard_map over the sequence axis; batch stays sharded over
the dp axes outside.  Used by every cached-attention family (GQA, MLA,
whisper self-attn, zamba shared block) via the runtime hook in
``repro.models.attention``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _local_flash(q, k, v, start, t):
    """Partial online softmax over this shard's KV slice.

    q: (B,Hkv,G,1,D) fp32 pre-scaled; k/v: (B,Hkv,S_loc,D);
    start: global position of k[..., 0, :]; t: current step (valid <= t).
    Returns m (B,Hkv,G,1,1), l, acc (B,Hkv,G,1,D).
    """
    with jax.named_scope("flash_inner"):  # VMEM-resident when kernelized
        s_loc = k.shape[2]
        scores = jnp.einsum(
            "bhgqd,bhkd->bhgqk", q, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        pos = start + jnp.arange(s_loc)
        scores = jnp.where((pos <= t)[None, None, None, None, :], scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        # guard all-masked shards: exp(-1e30 - (-1e30)) = 1 lanes must not count
        p = jnp.where((pos <= t)[None, None, None, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return m, l, acc


def sp_decode_attention(
    q: Array,          # (B, Hq, 1, D)
    k_cache: Array,    # (B, Hkv, S, D) — S sharded over `seq_axis`
    v_cache: Array,
    k_new: Array,      # (B, Hkv, 1, D)
    v_new: Array,
    t: Array,          # scalar int32 — write position / last valid position
    mesh: Mesh,
    *,
    seq_axis: str = "model",
    batch_spec=None,   # P entry for the batch dim (dp axes or None)
    scale: Optional[float] = None,
) -> Tuple[Array, Array, Array]:
    """Returns (attn_out (B,Hq,1,D), new_k_cache, new_v_cache)."""
    b, hq, _, d = q.shape
    hkv = k_cache.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s_global = k_cache.shape[2]
    n_shards = mesh.shape[seq_axis]
    s_loc = s_global // n_shards

    bs = batch_spec
    qspec = P(bs, None, None, None)
    cspec = P(bs, None, seq_axis, None)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(qspec, cspec, cspec, qspec, qspec, P()),
        out_specs=(qspec, cspec, cspec),
        check_vma=False,
    )
    def run(q, kc, vc, kn, vn, t):
        idx = jax.lax.axis_index(seq_axis)
        start = idx * s_loc
        # owning shard writes the new K/V at local position t - start
        local_t = jnp.clip(t - start, 0, s_loc - 1)
        owns = (t >= start) & (t < start + s_loc)
        kc_upd = jax.lax.dynamic_update_slice_in_dim(kc, kn, local_t, axis=2)
        vc_upd = jax.lax.dynamic_update_slice_in_dim(vc, vn, local_t, axis=2)
        kc = jnp.where(owns, kc_upd, kc)
        vc = jnp.where(owns, vc_upd, vc)

        qf = (q.astype(jnp.float32) * scale).reshape(b_loc := q.shape[0], hkv, group, 1, d)
        m, l, acc = _local_flash(qf, kc, vc, start, t)

        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr, seq_axis)
        out = acc_g / jnp.maximum(l_g, 1e-30)
        out = out.reshape(b_loc, hq, 1, d).astype(q.dtype)
        return out, kc, vc

    return run(q, k_cache, v_cache, k_new, v_new, jnp.asarray(t, jnp.int32))


def sp_decode_attention_mla(
    q_comb: Array,       # (B, H, 1, r+dr) — pre-scaled absorbed query
    ckv_cache: Array,    # (B, S, r) — S sharded over seq_axis
    krope_cache: Array,  # (B, 1, S, dr)
    c_new: Array,        # (B, 1, r)
    kr_new: Array,       # (B, 1, 1, dr)
    t: Array,
    mesh: Mesh,
    *,
    seq_axis: str = "model",
    batch_spec=None,
) -> Tuple[Array, Array, Array]:
    """MLA latent-cache decode with the same flash combine.

    Keys are the local concat(latent, rope-key); values are the latent —
    the attended latent is returned (B, H, 1, r) for the wkv_b
    up-projection outside.  The combine collective moves (B*H*(r)) floats.
    """
    b, h, _, dcomb = q_comb.shape
    r = ckv_cache.shape[-1]
    s_global = ckv_cache.shape[1]
    n_shards = mesh.shape[seq_axis]
    s_loc = s_global // n_shards

    bs = batch_spec
    qspec = P(bs, None, None, None)
    cspec = P(bs, seq_axis, None)
    kspec = P(bs, None, seq_axis, None)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(qspec, cspec, kspec, P(bs, None, None), qspec, P()),
        out_specs=(qspec, cspec, kspec),
        check_vma=False,
    )
    def run(qc, ckv, krope, cn, krn, t):
        idx = jax.lax.axis_index(seq_axis)
        start = idx * s_loc
        local_t = jnp.clip(t - start, 0, s_loc - 1)
        owns = (t >= start) & (t < start + s_loc)
        ckv_upd = jax.lax.dynamic_update_slice_in_dim(ckv, cn, local_t, axis=1)
        kr_upd = jax.lax.dynamic_update_slice_in_dim(krope, krn, local_t, axis=2)
        ckv = jnp.where(owns, ckv_upd, ckv)
        krope = jnp.where(owns, kr_upd, krope)

        keys = jnp.concatenate([ckv, krope[:, 0]], axis=-1)[:, None]  # (B,1,S_loc,r+dr)
        b_loc = qc.shape[0]
        qf = qc.astype(jnp.float32).reshape(b_loc, 1, h, 1, dcomb)
        m, l, acc = _local_flash(qf, keys, ckv[:, None], start, t)  # acc: (...,r)

        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr, seq_axis)
        out = (acc_g / jnp.maximum(l_g, 1e-30)).reshape(b_loc, h, 1, r)
        return out.astype(qc.dtype), ckv, krope

    return run(q_comb, ckv_cache, krope_cache, c_new, kr_new, jnp.asarray(t, jnp.int32))
