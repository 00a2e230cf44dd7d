"""Distributed (multi-device) DPP-PMRF via shard_map — a thin wrapper.

The paper's future work (§5, [15]) proposes combining DPP-PMRF with a
distributed-memory parallel PMRF for a hybrid-parallel approach.  This
module is that hybrid on a JAX device mesh — but it contains NO MAP/EM
loop of its own (DESIGN.md §11).  There is one driver
(``em._em_driver``), parametrized by a collective context
(``collectives.ReduceCtx``); this module only

  1. block-partitions hood *elements* across a mesh axis
     (:func:`partition_hoods` — host-side, shapes only depend on the
     shard count, so the result feeds AOT compilation), and
  2. ``shard_map``s the same driver with a sharded context
     (:func:`run_em_sharded`), which wraps the four cross-shard touch
     points in psum/pmin (see ``collectives.py``).

All three execution modes work sharded — ``faithful``, ``static``, and
``static-pallas`` (the fused kernel launches per shard; collectives stay
outside the kernel).  Labels and parameters stay replicated (they are
tiny: V+1 and 2 lanes), so every device takes the identical EM trajectory
— sharded labels are bit-identical to single-device (tested), and energies
agree to float-summation-order tolerance.

Partitioning is by *element block*, not by whole neighborhood: hood sums
use a global segment id space reduced with psum, so neighborhoods may
straddle shard boundaries freely.  This sidesteps the load-imbalance
problem the paper observes for the OpenMP outer-parallel code on irregular
neighborhood demographics (§4.3.3) — element blocks are perfectly balanced
by construction.  The faithful mode's label-replication arrays are
re-localized per shard (each element's two rep lanes live on the element's
shard, indexed block-locally), so its per-element SortByKey +
ReduceByKey(Min) stays entirely shard-local.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.pmrf import collectives
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as E
from repro.core.pmrf.em import EMConfig, EMResult
from repro.core.pmrf.hoods import Hoods

Array = jax.Array


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return np.concatenate([x, np.full((pad,) + x.shape[1:], fill, x.dtype)])


def partition_hoods(hoods: Hoods, n_shards: int) -> Hoods:
    """Prepare a ``Hoods`` for block-partitioned execution over ``n_shards``.

    Element arrays are padded so the capacity divides evenly into
    ``n_shards`` blocks of ``block = capacity / n_shards`` lanes (padding
    lanes carry the usual sentinels and are masked by ``valid``).  The
    label-replication arrays are *re-localized*: lane range
    ``[s * 2 * block, (s + 1) * 2 * block)`` holds exactly the rep lanes
    whose ``old_index`` falls in element block ``s``, with ``old_index``
    rebased to the block (each valid element contributes exactly two rep
    lanes, so ``2 * block`` lanes per shard always suffice).  Under
    ``shard_map`` with everything partitioned on the leading axis, each
    shard therefore sees a self-contained local ``Hoods`` whose
    ``vertex``/``hood_id`` still carry *global* ids (for the replicated
    gathers and the psum'd segment reductions).

    Host-side and shape-deterministic: the output shapes depend only on
    ``(capacity, n_shards)``, so the session layer can AOT-compile against
    them (DESIGN.md §10/§11).  The returned ``Hoods`` is only meaningful
    as input to :func:`run_em_sharded`.
    """
    if n_shards <= 1:
        return hoods
    cap = hoods.capacity
    block = -(-cap // n_shards)
    cap_pad = block * n_shards
    n_hoods, n_regions = hoods.n_hoods, hoods.n_regions

    vertex = _pad_to(np.asarray(hoods.vertex, np.int32), cap_pad, n_regions)
    hood_id = _pad_to(np.asarray(hoods.hood_id, np.int32), cap_pad, n_hoods)
    valid = _pad_to(np.asarray(hoods.valid, bool), cap_pad, False)

    rep_valid = np.asarray(hoods.rep_valid, bool)
    rep_old = np.asarray(hoods.rep_old_index, np.int64)
    rep_test = np.asarray(hoods.rep_test_label, np.int32)
    rep_hood = np.asarray(hoods.rep_hood_id, np.int32)

    out_old = np.full((2 * cap_pad,), block - 1, np.int32)
    out_test = np.zeros((2 * cap_pad,), np.int32)
    out_hood = np.full((2 * cap_pad,), n_hoods, np.int32)
    out_valid = np.zeros((2 * cap_pad,), bool)

    lanes = np.nonzero(rep_valid)[0]
    if lanes.size:
        shard = rep_old[lanes] // block
        order = np.argsort(shard, kind="stable")
        lanes, shard = lanes[order], shard[order]
        counts = np.bincount(shard, minlength=n_shards)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        rank = np.arange(lanes.size) - starts[shard]
        if rank.size and int(rank.max()) >= 2 * block:
            raise AssertionError(
                "replication overflow: an element block received more than "
                "2*block rep lanes — hoods invariant violated"
            )
        pos = shard * (2 * block) + rank
        out_old[pos] = (rep_old[lanes] - shard * block).astype(np.int32)
        out_test[pos] = rep_test[lanes]
        out_hood[pos] = rep_hood[lanes]
        out_valid[pos] = True

    return Hoods(
        vertex=jnp.asarray(vertex),
        hood_id=jnp.asarray(hood_id),
        valid=jnp.asarray(valid),
        sizes=hoods.sizes,
        offsets=hoods.offsets,
        n_hoods=n_hoods,
        n_regions=n_regions,
        n_elements=hoods.n_elements,
        rep_old_index=jnp.asarray(out_old),
        rep_test_label=jnp.asarray(out_test),
        rep_hood_id=jnp.asarray(out_hood),
        rep_valid=jnp.asarray(out_valid),
    )


@partial(jax.jit, static_argnames=("config", "mesh", "axis"))
def run_em_sharded(
    hoods: Hoods,
    model: E.EnergyModel,
    labels0: Array,
    mu0: Array,
    sigma0: Array,
    *,
    config: EMConfig,
    mesh: Mesh,
    axis: str = "data",
) -> EMResult:
    """``shard_map`` the unified EM driver over ``mesh[axis]``.

    ``hoods`` must come from :func:`partition_hoods` for the mesh's shard
    count (capacity divisible by the axis size, rep arrays localized).
    Supports every execution mode; the fused static-pallas kernel runs
    once per shard with the collectives outside the launch.
    """
    if config.mode not in em_mod.MODES:
        raise ValueError(f"unknown mode {config.mode!r}; have {em_mod.MODES}")
    nsh = mesh.shape[axis]
    if hoods.capacity % nsh:
        raise ValueError(
            f"hoods capacity {hoods.capacity} not divisible by {nsh} shards; "
            "call partition_hoods(hoods, n_shards) first"
        )
    em_mod.TRACE_COUNTS["run_em_sharded"] += 1
    n_hoods, n_regions = hoods.n_hoods, hoods.n_regions
    ctx = collectives.ReduceCtx(axis=axis)
    spec_e = P(axis)      # element-partitioned
    spec_r = P()          # replicated

    # check_vma=False: a pallas_call inside shard_map declares no
    # varying-axes type for its outputs, which the check requires.  The
    # outputs are replicated by construction (every cross-shard quantity
    # goes through the collective context).
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_e,) * 7 + (spec_r,) * 4,
        out_specs=spec_r,
        check_vma=False,
    )
    def run(
        vertex, hood_id, valid, rep_old, rep_test, rep_hood, rep_valid,
        labels0, mu0, sigma0, model_arrays,
    ):
        local = Hoods(
            vertex=vertex,
            hood_id=hood_id,
            valid=valid,
            sizes=jnp.zeros((n_hoods,), jnp.int32),      # unused by the driver
            offsets=jnp.zeros((n_hoods + 1,), jnp.int32),
            n_hoods=n_hoods,
            n_regions=n_regions,
            n_elements=-1,
            rep_old_index=rep_old,
            rep_test_label=rep_test,
            rep_hood_id=rep_hood,
            rep_valid=rep_valid,
        )
        lmodel = E.EnergyModel(*model_arrays)
        return em_mod._em_driver(local, lmodel, labels0, mu0, sigma0, config, ctx)

    return run(
        hoods.vertex, hoods.hood_id, hoods.valid,
        hoods.rep_old_index, hoods.rep_test_label, hoods.rep_hood_id,
        hoods.rep_valid, labels0, mu0, sigma0, tuple(model),
    )


def distributed_em(
    hoods: Hoods,
    model: E.EnergyModel,
    labels0: Array,
    mu0: Array,
    sigma0: Array,
    mesh: Mesh,
    axis: str = "data",
    config: EMConfig = EMConfig(),
) -> EMResult:
    """Run EM with hood elements sharded over ``mesh[axis]`` (any mode).

    Convenience wrapper: partition + shard_map'd unified driver.  The
    session layer (``repro.api``) calls the two pieces separately so the
    partitioned inputs can be memoized and the program AOT-compiled.
    """
    parts = partition_hoods(hoods, mesh.shape[axis])
    return run_em_sharded(
        parts, model, labels0, mu0, sigma0, config=config, mesh=mesh, axis=axis
    )
