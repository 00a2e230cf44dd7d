"""Known-bad Pallas kernel corpus for the static checker (DESIGN.md §15).

Three deliberately defective toy kernels, each constructed so that
EXACTLY ONE detector class fires — they are negative controls for
``repro.analysis.pallas_check``:

* :func:`racy_jaxpr` — the output block is revisited along a grid axis
  *declared parallel* (PL101; the write-write race class);
* :func:`oob_jaxpr` — the output index map walks one block past the end
  of the array (PL102);
* :func:`nondivisible_jaxpr` — the block shape does not divide the
  output array shape (PL103);
* :func:`undeclared_jaxpr` — a revisited output with NO declared
  dimension semantics (PL104; what every kernel in ``src/repro/kernels``
  looked like before the semantics declarations landed — this fixture
  pins that fix).

The kernels are only ever *traced* (``jax.make_jaxpr``), never run, so
the racy/oob bodies are harmless.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import tpu_compiler_params

_N = 128
_BLOCK = 64


def _copy_body(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _trace(fn):
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((_N,), jnp.float32))


def racy_jaxpr():
    """Output revisited along grid axis 0, which is declared parallel."""

    def fn(x):
        return pl.pallas_call(
            _copy_body,
            grid=(4, _N // _BLOCK),
            in_specs=[pl.BlockSpec((_BLOCK,), lambda i, j: (j,))],
            # index map ignores i -> the same output block is written at
            # every i; i is declared parallel -> race.
            out_specs=pl.BlockSpec((_BLOCK,), lambda i, j: (j,)),
            out_shape=jax.ShapeDtypeStruct((_N,), jnp.float32),
            compiler_params=tpu_compiler_params("parallel", "parallel"),
        )(x)

    return _trace(fn)


def oob_jaxpr():
    """Output index map yields block index 2 on a 2-block array."""

    def fn(x):
        return pl.pallas_call(
            _copy_body,
            grid=(_N // _BLOCK,),
            in_specs=[pl.BlockSpec((_BLOCK,), lambda i: (i,))],
            out_specs=pl.BlockSpec((_BLOCK,), lambda i: (i + 1,)),
            out_shape=jax.ShapeDtypeStruct((_N,), jnp.float32),
            compiler_params=tpu_compiler_params("parallel"),
        )(x)

    return _trace(fn)


def nondivisible_jaxpr():
    """64-wide blocks over a 96-element output: a remainder tile."""

    def fn(x):
        return pl.pallas_call(
            _copy_body,
            grid=(2,),
            in_specs=[pl.BlockSpec((_BLOCK,), lambda i: (i,))],
            out_specs=pl.BlockSpec((_BLOCK,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((96,), jnp.float32),
            compiler_params=tpu_compiler_params("parallel"),
        )(x)

    return _trace(fn)


def undeclared_jaxpr():
    """Revisited output with no dimension_semantics declared at all."""

    def fn(x):
        return pl.pallas_call(
            _copy_body,
            grid=(4, _N // _BLOCK),
            in_specs=[pl.BlockSpec((_BLOCK,), lambda i, j: (j,))],
            out_specs=pl.BlockSpec((_BLOCK,), lambda i, j: (j,)),
            out_shape=jax.ShapeDtypeStruct((_N,), jnp.float32),
        )(x)

    return _trace(fn)
