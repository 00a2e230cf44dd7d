"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

The TPU compiler ships with JAX, so lowering each kernel with
``interpret=False`` against a ``v5e:2x2`` topology description runs Mosaic
exactly as on the chip and fails on what the chip would refuse: tile
layouts, unsupported vector ops, VMEM overuse.  Interpret-mode parity
tests cannot see any of that.  Nothing runs, so these tests say nothing
about results or time.

Shapes are those of the fused path at the paper's 512x512 slice with a
16x16 oversegmentation (bucket capacity 15616, 448 hoods, 256 regions),
the bucket ``chip_smoke.py`` runs the fused EM tick at.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import em_tick, map_step, segment_reduce

H, N_HOODS, N_VERTICES = 15616, 448, 257


@pytest.fixture(scope="module")
def one_chip():
    # Skip only where the TPU compiler is not installed at all; any other
    # failure to describe the topology fails the tests.
    pytest.importorskip("libtpu", reason="the TPU compiler (libtpu) is not installed")
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, args, sharding) -> str:
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_lines(text: str, name: str):
    return [l for l in text.splitlines() if "tpu_custom_call" in l and name in l]


@pytest.mark.parametrize(
    "n_labels,precision", [(2, "f32"), (5, "f32"), (2, "bf16")]
)
def test_fused_em_tick_compiles_for_v5e(one_chip, n_labels, precision):
    f32, i32 = jnp.float32, jnp.int32
    fn = functools.partial(
        em_tick.fused_em_tick_pallas, n_hoods=N_HOODS, n_vertices=N_VERTICES,
        precision=precision, interpret=False,
    )
    args = (
        [((H,), f32)] * 5 + [((H,), i32)] * 2 + [((N_VERTICES,), f32)] * 2
        + [((4, N_HOODS), f32), ((n_labels,), f32), ((n_labels,), f32), ((), f32)]
    )
    assert _kernel_lines(_compile_text(fn, args, one_chip), "fused_em_tick")


def test_fused_map_step_compiles_for_v5e(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    fn = functools.partial(
        map_step.fused_map_step_pallas, n_hoods=N_HOODS, n_vertices=N_VERTICES,
        interpret=False,
    )
    args = (
        [((H,), f32), ((H,), f32), ((2, H), f32)] + [((H,), f32)] * 3
        + [((H,), i32)] * 2 + [((2,), f32), ((2,), f32), ((), f32)]
    )
    assert _kernel_lines(_compile_text(fn, args, one_chip), "fused_map_step")


@pytest.mark.parametrize("op", ["add", "min"])
def test_segment_reduce_compiles_for_v5e_at_1024_segments(one_chip, op):
    fn = functools.partial(
        segment_reduce.segment_reduce_pallas, num_segments=1024, op=op,
        interpret=False,
    )
    args = [((H,), jnp.float32), ((H,), jnp.int32)]
    assert _kernel_lines(_compile_text(fn, args, one_chip), "segment_reduce")
