"""Pallas TPU kernel: online-softmax (flash) attention with GQA.

The LM stack's prefill hot spot.  Classic single-pass formulation: the
grid walks (batch, q-head, q-block, kv-block) with the kv-block innermost;
running max / normalizer / weighted accumulator live in VMEM scratch and
are rescaled per kv step, so the (S x S) score matrix never materializes
in HBM — this is what makes the 32k prefill shapes fit (DESIGN.md §6).

GQA is handled in the BlockSpec index maps: the kv specs map q-head h to
kv-head h // group, so no repeated K/V copies are made.

Validated in interpret mode against ``ref.flash_attention`` over shape /
dtype / causality sweeps (tests/test_kernels.py); on TPU the same
pallas_call lowers to MXU matmuls with (Bq x D) and (Bk x D) VMEM tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1.0e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, n_k: int
):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (Bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (Bk, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                     # (Bq, Bk)

    if causal:
        q_pos = i_q * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = i_k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]                           # (Bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                        # (Bq, Bk)
    corr = jnp.exp(m_prev - m_new)                # (Bq, 1)

    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(i_k == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = True,
) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D); returns (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q = s // block_q
    n_k = s // block_k

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
    )

    return pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h, iq, ik: (b_, h // group, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h, iq, ik: (b_, h // group, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # The output tile is revisited along the key-block axis ik (the
        # online-softmax accumulation), so ik must be sequential
        # ("arbitrary"); batch/head/query-block axes write disjoint
        # tiles and are parallel.  Declared for the analysis race
        # checker (PL101/PL104, DESIGN.md §15).
        compiler_params=tpu_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"
        ),
        interpret=interpret,
    )(q, k, v)
