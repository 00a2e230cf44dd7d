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
the bucket ``chip_smoke.py`` runs the fused EM tick at; and, for the
neighbourhood program and the ``static`` EM executable, the bucket of the
full 1813x1830 slice (``bench/configs/fullslice1813x1830-g227x229.json``).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.api import session as session_mod
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import hoods as hoods_mod
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


FULL_SLICE = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
              / "fullslice1813x1830-g227x229.json")


def _full_slice():
    cfg = json.loads(FULL_SLICE.read_text())
    return cfg, session_mod.BucketKey(*cfg["bucket"]["value"])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def test_hood_program_compiles_for_v5e_at_the_full_slice_class(one_chip):
    # The class every derived full slice lands in: key lanes and the padded
    # CSR at the bucket capacity, the cliques at its hood count.
    cfg, bucket = _full_slice()
    gy, gx = cfg["overseg_grid"]
    i32 = jnp.int32
    shapes = [((bucket.capacity,), i32)] * 2 + [
        ((gy * gx + 1,), i32), ((bucket.capacity,), i32), ((), i32), ((), i32)]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = hoods_mod._hood_arrays.lower(
        *specs, n_cliques=bucket.n_hoods, n_regions=gy * gx,
        n_lanes=bucket.capacity).compile()
    assert "sort" in compiled.as_text()


def test_static_em_compiles_for_v5e_at_the_full_slice_bucket(one_chip):
    cfg, bucket = _full_slice()
    inputs = _on(one_chip, session_mod._abstract_inputs(bucket, None, 1, cfg["n_labels"]))
    em_config = api.ExecutionConfig(
        mode=cfg["mode"], max_em_iters=cfg["max_em_iters"],
        max_map_iters=cfg["max_map_iters"]).em_config(backend="pallas-tpu")
    compiled = em_mod.run_em.lower(*inputs, em_config).compile()
    # About 0.1 GB of temporaries at a full slice, of the chip's 16 GB.
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
