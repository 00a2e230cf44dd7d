"""Pallas TPU kernel: fused MRF energy evaluation + per-element label min.

Fuses the paper's "Compute Energy Function" Map and the "Compute Minimum
Vertex and Label Energies" SortByKey+ReduceByKey(Min) into a single
VMEM-resident pass for the binary-label case: per element, both label
energies are computed in registers and reduced immediately — the (2, H)
replicated energy array never round-trips to HBM, and the per-iteration
sort disappears entirely (DESIGN.md §2, the static-mode optimization taken
to the kernel level).

Inputs are the pre-gathered per-element arrays (all shape (H,)):
  y      region mean intensity
  w      region weight (0 on padding lanes)
  n1_e   label-1 count of the element's neighborhood
  nall_e neighborhood size
  xf     element's current label as float
and the scalar parameter vector  params = [mu0, mu1, sig0, sig1, beta].

Outputs: min_e (H,) float32, arg (H,) int32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import tpu_compiler_params

BLOCK = 2048  # elements per tile (VMEM: ~7 input/output f32 lanes * BLOCK)


def _kernel(params_ref, y_ref, w_ref, n1_ref, nall_ref, xf_ref, min_ref, arg_ref):
    mu0 = params_ref[0]
    mu1 = params_ref[1]
    sig0 = params_ref[2]
    sig1 = params_ref[3]
    beta = params_ref[4]

    y = y_ref[...]
    w = w_ref[...]
    n1 = n1_ref[...]
    nall = nall_ref[...]
    xf = xf_ref[...]

    denom = jnp.maximum(nall - 1.0, 1.0)

    d0 = y - mu0
    e0 = w * (d0 * d0 / (2.0 * sig0 * sig0) + jnp.log(sig0))
    e0 = e0 + beta * jnp.maximum(n1 - xf, 0.0) / denom

    d1 = y - mu1
    e1 = w * (d1 * d1 / (2.0 * sig1 * sig1) + jnp.log(sig1))
    e1 = e1 + beta * jnp.maximum((nall - n1) - (1.0 - xf), 0.0) / denom

    min_ref[...] = jnp.minimum(e0, e1)
    arg_ref[...] = (e1 < e0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mrf_min_energy_pallas(
    y: jax.Array,
    w: jax.Array,
    n1_e: jax.Array,
    nall_e: jax.Array,
    xf: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    beta,
    *,
    interpret: bool,
):
    n = y.shape[0]
    n_pad = -(-n // BLOCK) * BLOCK

    def pad(x, fill=0.0):
        return jnp.full((n_pad,), fill, jnp.float32).at[:n].set(x.astype(jnp.float32))

    params = jnp.stack(
        [mu[0], mu[1], sigma[0], sigma[1], jnp.asarray(beta, jnp.float32)]
    ).astype(jnp.float32)

    min_e, arg = pl.pallas_call(
        _kernel,
        grid=(n_pad // BLOCK,),
        in_specs=[
            pl.BlockSpec((5,), lambda i: (0,)),  # broadcast scalar params
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.float32),
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        ],
        # One element block per grid step, no output revisited — the
        # grid is safe to parallelize, and saying so lets Mosaic do it
        # (declared for the analysis race checker, DESIGN.md §15).
        compiler_params=tpu_compiler_params("parallel"),
        interpret=interpret,
        name="mrf_min_energy",
    )(params, pad(y), pad(w), pad(n1_e), pad(nall_e), pad(xf))

    return min_e[:n], arg[:n]
