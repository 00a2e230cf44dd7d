"""Pallas TPU kernel: block-tiled segmented reduction (ReduceByKey).

The paper's own profiling identifies ReduceByKey (with SortByKey) as the
scalability bottleneck of the vendor DPP implementations (§4.3.2/4.3.3).
The TPU-native rethink: with segment ids known (the static-structure
optimization, DESIGN.md §2), ReduceByKey becomes a *masked one-hot
contraction* that runs on the MXU instead of a scatter/sort pipeline:

    out[s] = reduce_i  (seg[i] == s) ? v[i] : identity

The kernel tiles segments x values on a 2D grid; each step builds the
(BS x BN) one-hot tile in VMEM from an iota comparison and contracts it
with the value block — `add` uses an MXU dot, `min` a masked VPU min —
accumulating over the value-block (minor) grid dimension.

Padding convention: out-of-range segment ids (>= num_segments) never match
a one-hot row, so callers pad values with anything and ids with 2**30.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import tpu_compiler_params

# BS: segment rows per tile.  XLA lays out a 1-D f32 array of 1024 or
# more elements in T(1024) tiles, and Mosaic requires the output block to
# match, so the segment axis is tiled (and padded) in 1024s.
BLOCK_SEG = 1024
BLOCK_VAL = 1024  # BN: value lanes per tile


def _kernel_add(seg_ref, val_ref, out_ref):
    i_s = pl.program_id(0)
    i_v = pl.program_id(1)

    @pl.when(i_v == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]            # (BN,)
    val = val_ref[...]            # (BN,)
    s_base = i_s * BLOCK_SEG
    rows = s_base + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_SEG, BLOCK_VAL), 0)
    onehot = (rows == seg[None, :]).astype(val.dtype)   # (BS, BN)
    out_ref[...] += jnp.dot(onehot, val, preferred_element_type=out_ref.dtype)


def _kernel_min(seg_ref, val_ref, out_ref):
    i_s = pl.program_id(0)
    i_v = pl.program_id(1)
    # +inf matches jax.ops.segment_min's empty-segment identity.
    big = jnp.asarray(jnp.inf, out_ref.dtype)

    @pl.when(i_v == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, big)

    seg = seg_ref[...]
    val = val_ref[...]
    s_base = i_s * BLOCK_SEG
    rows = s_base + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_SEG, BLOCK_VAL), 0)
    onehot = rows == seg[None, :]
    masked = jnp.where(onehot, val[None, :], big)       # (BS, BN)
    out_ref[...] = jnp.minimum(out_ref[...], jnp.min(masked, axis=1))


@functools.partial(
    jax.jit, static_argnames=("num_segments", "op", "interpret")
)
def segment_reduce_pallas(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "add",
    *,
    interpret: bool,
) -> jax.Array:
    """Segmented reduction via pl.pallas_call.  1D float values only."""
    n = values.shape[0]
    n_pad = -(-n // BLOCK_VAL) * BLOCK_VAL
    s_pad = -(-num_segments // BLOCK_SEG) * BLOCK_SEG

    vals = jnp.zeros((n_pad,), values.dtype).at[:n].set(values)
    segs = jnp.full((n_pad,), 2 ** 30, jnp.int32).at[:n].set(
        segment_ids.astype(jnp.int32)
    )

    kernel = _kernel_add if op == "add" else _kernel_min
    out = pl.pallas_call(
        kernel,
        grid=(s_pad // BLOCK_SEG, n_pad // BLOCK_VAL),
        in_specs=[
            pl.BlockSpec((BLOCK_VAL,), lambda i, j: (j,)),
            pl.BlockSpec((BLOCK_VAL,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((BLOCK_SEG,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((s_pad,), values.dtype),
        # The output block is revisited along the value axis j (the
        # accumulation axis), which must therefore run sequentially
        # ("arbitrary"); the segment-block axis i writes disjoint output
        # blocks and is declared parallel.  Stated explicitly for the
        # analysis race checker (PL101/PL104, DESIGN.md §15) instead of
        # leaning on Mosaic's implicit sequential default.
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="segment_reduce",
    )(segs, vals)
    return out[:num_segments]
