"""Pallas TPU kernel: fully-fused K-ary MAP iteration inner step.

The paper's MAP iteration is a chain of DPPs — Map (energy), SortByKey +
ReduceByKey(Min) (per-element label min), ReduceByKey(Add) (per-hood energy
sums), Scatter (label votes) — and its own profiling (§4.3.2) pins the
scaling ceiling on the keyed primitives.  This kernel fuses the *entire*
iteration body into one launch, for any label count K (DESIGN.md §13):

    per element e, label l:  e_l      (energy — registers only)
    per element e:           min_e    = min_l e_l
                             arg      = argmin_l e_l  (ties -> lowest l)
    per hood h:      hood_e[h]   = sum_{e in h} min_e[e]         (one-hot dot)
    per (l, vertex): votes[l,v]  = #{e: vertex[e]=v, arg[e]=l}   (one-hot dot)

The grid walks the element blocks only; all K labels of a block are
computed at once as a ``(K, BLOCK)`` tile from the block's slice of the
per-(element, label) neighborhood-count input and the ``(K, 1)`` mu/sigma
columns (the energy expression is ``em_tick.label_energies_blocked``, the
same one the fused EM tick runs).  An unrolled fold over the K rows gives
the per-element min/argmin, and the keyed reductions run as masked
one-hot contractions on the MXU — including one vote contraction per
label into the (K, n_vertices) vote field.  The K=2 instance computes
bit-identical energies, argmins, hood sums, and votes to the historical
binary kernel (the count rewrite only touches integer-exact quantities).

Inputs (all (H,) unless noted):
  y       region mean intensity (pre-gathered per element)
  w       region weight, 0 on padding lanes
  cnt_e   (K, H) per-element count of each label in the element's hood
  nall_e  neighborhood size (EM-invariant, hoisted by the caller)
  xf      element's current label as float
  valid   1.0 on real hood elements, 0.0 on padding
  hood_id / vertex  (H,) int32 segment ids for the two reductions
  mu, sigma  (K,) label parameters; beta  scalar smoothness weight

Outputs: min_e (H,) f32, arg (H,) i32, hood_e (n_hoods,) f32,
votes (K, n_vertices) f32.

Padding convention matches ``segment_reduce.py``: ids >= the padded segment
count never match a one-hot row, so lanes masked out by ``valid`` (which
zeroes their contributions anyway) and block-padding lanes (ids forced to
2**30) are both inert.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import tpu_compiler_params
from repro.kernels.em_tick import fold_min_argmin, label_energies_blocked

BLOCK = 1024     # hood elements per value tile
SEG_ALIGN = 128  # segment-axis padding (MXU lane width)


def _kernel(
    beta_ref,
    mu_ref,
    sig_ref,
    y_ref,
    w_ref,
    cnt_ref,
    nall_ref,
    xf_ref,
    valid_ref,
    hood_ref,
    vert_ref,
    min_ref,
    arg_ref,
    hood_e_ref,
    votes_ref,
    *,
    n_labels: int,
):
    i_v = pl.program_id(0)
    valid = valid_ref[...]

    # (K, BLOCK) energies, op order identical to energy.label_energies so
    # the per-element argmin is bit-identical to the static mode.
    e = label_energies_blocked(
        y_ref[...], w_ref[...], cnt_ref[...], nall_ref[...], xf_ref[...],
        valid, mu_ref[...], sig_ref[...], beta_ref[0],
    )
    min_e, arg = fold_min_argmin(e)
    min_ref[...] = min_e
    arg_ref[...] = arg

    @pl.when(i_v == 0)
    def _init():
        hood_e_ref[...] = jnp.zeros_like(hood_e_ref)
        votes_ref[...] = jnp.zeros_like(votes_ref)

    # Keyed reductions as one-hot contractions (MXU); the element-block
    # axis is sequential, so += accumulation is safe.
    s_rows = hood_e_ref.shape[0]
    rows_h = jax.lax.broadcasted_iota(jnp.int32, (s_rows, BLOCK), 0)
    onehot_h = (rows_h == hood_ref[...][None, :]).astype(jnp.float32)
    hood_e_ref[...] += jnp.dot(
        onehot_h, min_e * valid, preferred_element_type=jnp.float32
    )

    v_rows = votes_ref.shape[1]
    rows_v = jax.lax.broadcasted_iota(jnp.int32, (v_rows, BLOCK), 0)
    onehot_v = (rows_v == vert_ref[...][None, :]).astype(jnp.float32)
    for l2 in range(n_labels):
        sel = (arg == l2).astype(jnp.float32) * valid
        votes_ref[l2, :] += jnp.dot(
            onehot_v, sel, preferred_element_type=jnp.float32
        )


@functools.partial(
    jax.jit, static_argnames=("n_hoods", "n_vertices", "interpret")
)
def fused_map_step_pallas(
    y: jax.Array,
    w: jax.Array,
    cnt_e: jax.Array,
    nall_e: jax.Array,
    xf: jax.Array,
    valid: jax.Array,
    hood_id: jax.Array,
    vertex: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    beta,
    *,
    n_hoods: int,
    n_vertices: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused launch for the whole static-mode K-ary MAP iteration body."""
    n_labels = int(mu.shape[0])
    n = y.shape[0]
    n_pad = -(-n // BLOCK) * BLOCK
    s_pad = -(-n_hoods // SEG_ALIGN) * SEG_ALIGN
    r_pad = -(-n_vertices // SEG_ALIGN) * SEG_ALIGN

    def padf(x):
        return jnp.zeros((n_pad,), jnp.float32).at[:n].set(x.astype(jnp.float32))

    def padi(x):
        return jnp.full((n_pad,), 2 ** 30, jnp.int32).at[:n].set(
            x.astype(jnp.int32)
        )

    cnt_pad = jnp.zeros((n_labels, n_pad), jnp.float32).at[:, :n].set(
        cnt_e.astype(jnp.float32)
    )

    blockspec_e = pl.BlockSpec((BLOCK,), lambda i: (i,))

    def full(shape):
        return pl.BlockSpec(shape, lambda i, _z=(0,) * len(shape): _z)

    min_e, arg, hood_e, votes = pl.pallas_call(
        functools.partial(_kernel, n_labels=n_labels),
        grid=(n_pad // BLOCK,),
        in_specs=[
            full((1,)),                                  # beta
            full((n_labels, 1)),                         # mu (column)
            full((n_labels, 1)),                         # sigma (column)
            blockspec_e,                                 # y
            blockspec_e,                                 # w
            pl.BlockSpec((n_labels, BLOCK), lambda i: (0, i)),  # cnt_e
            blockspec_e,                                 # nall_e
            blockspec_e,                                 # xf
            blockspec_e,                                 # valid
            blockspec_e,                                 # hood_id
            blockspec_e,                                 # vertex
        ],
        out_specs=[
            blockspec_e,                                 # min_e
            blockspec_e,                                 # arg
            full((s_pad,)),                              # accumulated
            full((n_labels, r_pad)),                     # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.float32),
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((s_pad,), jnp.float32),
            jax.ShapeDtypeStruct((n_labels, r_pad), jnp.float32),
        ],
        # hood_e/votes accumulate over the element-block axis, so it runs
        # sequentially ("arbitrary") — declared explicitly for the analysis
        # race checker (PL104, DESIGN.md §15).
        compiler_params=tpu_compiler_params("arbitrary"),
        interpret=interpret,
        name="fused_map_step",
    )(
        jnp.asarray(beta, jnp.float32).reshape(1),
        mu.astype(jnp.float32).reshape(n_labels, 1),
        sigma.astype(jnp.float32).reshape(n_labels, 1),
        padf(y),
        padf(w),
        cnt_pad,
        padf(nall_e),
        padf(xf),
        padf(valid),
        padi(hood_id),
        padi(vertex),
    )

    return min_e[:n], arg[:n], hood_e[:n_hoods], votes[:, :n_vertices]
