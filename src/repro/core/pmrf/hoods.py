"""k=1 neighborhood construction from maximal cliques (paper §3.2.2).

Implements the paper's four data-parallel steps verbatim on top of the DPP
layer:

  1. **Find Neighbors** (Map): per clique-member slot, count 1-hop
     neighbors that are not members of the slot's clique.
  2. **Count Neighbors** (Scan): prefix-sum the counts to allocate the
     neighborhoods array (static capacity computed host-side — the XLA
     static-shape adaptation, DESIGN.md §2).
  3. **Get Neighbors** (Map): populate candidate (cliqueId, vertexId)
     elements via the expand idiom (Scatter + max-Scan + Gather).
  4. **Remove Duplicate Neighbors** (SortByKey + Unique): sort candidates
     by (cliqueId, vertexId) compound key, drop adjacent duplicates.

It also builds the paper's label-replication index arrays (testLabel,
oldIndex, hoodId — the "repHoods" simulated, memory-free Gather).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dpp
from repro.core.pmrf.cliques import CliqueSet
from repro.core.pmrf.graph import RegionGraph


@jax.tree_util.register_dataclass
@dataclass
class Hoods:
    """Flat neighborhood arrays (static-shape padded).

    Padding lanes carry ``vertex == n_regions`` / ``hood_id == n_hoods`` so
    gathers stay in-bounds against sentinel-extended region arrays.
    """

    vertex: jnp.ndarray        # (H_pad,) int32 — vertex id per hood element
    hood_id: jnp.ndarray       # (H_pad,) int32 — neighborhood id per element
    valid: jnp.ndarray         # (H_pad,) bool
    sizes: jnp.ndarray         # (n_hoods,) int32
    offsets: jnp.ndarray       # (n_hoods + 1,) int32 (over the packed prefix)
    n_hoods: int = field(metadata=dict(static=True))
    n_regions: int = field(metadata=dict(static=True))
    n_elements: int = field(metadata=dict(static=True))  # valid-element count
    # Label-replication arrays (paper layout: per hood, label-0 block then
    # label-1 block), each (2 * H_pad,):
    rep_old_index: jnp.ndarray
    rep_test_label: jnp.ndarray
    rep_hood_id: jnp.ndarray
    rep_valid: jnp.ndarray

    @property
    def capacity(self) -> int:
        return int(self.vertex.shape[0])


def build_hoods(graph: RegionGraph, cliques: CliqueSet) -> Hoods:
    n = graph.n_regions
    c = cliques.n_cliques
    if c == 0:
        raise ValueError("no cliques — empty graph?")

    # Step 2's static capacity, counted on the host from the graph: one
    # candidate per (clique member, 1-hop neighbor) pair.
    members = cliques.members
    deg = np.diff(graph.csr_offsets)
    neighbor_capacity = int(deg[members[members >= 0]].sum())

    # Every shape below depends on the slice, so the device work is one
    # jitted program (one compile per slice shape) rather than dozens of
    # eagerly dispatched ops that each compile per shape.
    vertex, hood_id, valid, sizes, hood_offsets, rep = _hood_arrays(
        jnp.asarray(members),
        jnp.asarray(graph.csr_offsets),
        jnp.asarray(graph.csr_neighbors),
        n_regions=n,
        neighbor_capacity=neighbor_capacity,
    )
    return Hoods(
        vertex=vertex,
        hood_id=hood_id,
        valid=valid,
        sizes=sizes,
        offsets=hood_offsets,
        n_hoods=c,
        n_regions=n,
        n_elements=int(np.asarray(jnp.sum(valid.astype(jnp.int32)))),
        rep_old_index=rep[0],
        rep_test_label=rep[1],
        rep_hood_id=rep[2],
        rep_valid=rep[3],
    )


@functools.partial(jax.jit, static_argnames=("n_regions", "neighbor_capacity"))
def _hood_arrays(
    members, offsets, neighbors, *, n_regions: int, neighbor_capacity: int
):
    """Steps 1, 3 and 4 plus the label replication, for :func:`build_hoods`."""
    n = n_regions
    c, w = members.shape
    members_flat = members.reshape(-1)                # (C*W,)
    clique_of_slot = jnp.repeat(jnp.arange(c, dtype=jnp.int32), w)
    valid_slot = members_flat >= 0
    n_slots = c * w
    deg = offsets[1:] - offsets[:-1]

    safe_member = jnp.where(valid_slot, members_flat, 0)

    # -- Step 1: Find Neighbors (Map) — per-slot neighbor counts. ----------
    slot_counts = jnp.where(valid_slot, deg[safe_member], 0).astype(jnp.int32)

    # -- Step 3: Get Neighbors (Map over expanded lanes). ------------------
    src_slot, rank = dpp.expand_with_rank(slot_counts, neighbor_capacity)
    lane_valid = src_slot < n_slots
    safe_slot = jnp.minimum(src_slot, n_slots - 1)
    v = safe_member[safe_slot]
    nb = neighbors[jnp.minimum(offsets[v] + rank, neighbors.shape[0] - 1)]
    cid = clique_of_slot[safe_slot]
    # Exclude neighbors that are members of the same clique (paper step 1's
    # "not a member of the vertex's maximal clique" filter).
    nb_in_clique = jnp.any(members[cid] == nb[:, None], axis=1)
    cand_valid_nb = lane_valid & ~nb_in_clique

    # Clique members are hood elements too (hood = clique U 1-hop neighbors).
    member_keys_cid = clique_of_slot
    member_keys_v = safe_member

    span = n + 1
    sentinel = c * span + n  # decodes to (hood_id=c, vertex=n)

    # compound_key verifies the (cliqueId+1, vertexId+1) key space fits the
    # enabled integer width (int32 when jax_enable_x64 is off) instead of
    # silently wrapping — the sentinel (c, n) is the largest key we pack.
    key_nb = jnp.where(
        cand_valid_nb, dpp.compound_key(cid, nb, span, major_span=c + 1), sentinel
    )
    key_mem = jnp.where(
        valid_slot,
        dpp.compound_key(member_keys_cid, member_keys_v, span, major_span=c + 1),
        sentinel,
    )
    keys = jnp.concatenate([key_mem, key_nb])  # (neighbor_capacity + n_slots,)

    # -- Step 4: Remove Duplicate Neighbors (SortByKey + Unique). ----------
    (sorted_keys,) = dpp.sort_by_key(keys)
    uniq, count = dpp.unique_(sorted_keys, fill=sentinel)
    # Padding lanes of unique_ carry ``fill``; also drop the sentinel itself
    # if it survived as a "unique" value.
    lane = jnp.arange(uniq.shape[0])
    uniq = jnp.where((lane < count) & (uniq != sentinel), uniq, sentinel)

    hood_id = (uniq // span).astype(jnp.int32)
    vertex = (uniq % span).astype(jnp.int32)
    valid = uniq != sentinel

    sizes = dpp.reduce_by_key(
        jnp.where(valid, hood_id, c),
        valid.astype(jnp.int32),
        c + 1,
        op="add",
    )[:c]
    hood_offsets = dpp.counts_to_offsets(sizes)

    # -- Replication by label (paper: Map + Scan + Gather, memory-free). ---
    h_pad = int(vertex.shape[0])
    rep = _build_replication(hood_id, valid, sizes, hood_offsets, c, h_pad)
    return vertex, jnp.where(valid, hood_id, c), valid, sizes, hood_offsets, rep


def pad_hoods(
    h: Hoods,
    *,
    capacity: int,
    n_hoods: int,
    n_regions: int,
    n_elements: int | None = None,
) -> Hoods:
    """Pad a ``Hoods`` to a shared (capacity, n_hoods, n_regions) bucket.

    Enables the batched multi-slice path (DESIGN.md §9): every slice in a
    stack is padded to the same static shapes so one ``run_em`` trace (and
    one XLA program) serves the whole stack via ``vmap``.  Padding lanes
    carry the bucket's sentinels (``vertex == n_regions``,
    ``hood_id == n_hoods``) and are masked by ``valid``; phantom hoods
    (ids >= the slice's real hood count) have size 0 and accumulate exact
    zeros in every keyed reduction, so per-slice results are unchanged.

    ``n_elements`` is informational metadata (valid-element count) but part
    of the static treedef; stacking slices with different counts requires a
    shared override — the batched path passes ``-1`` ("mixed stack").
    """
    if capacity < h.capacity or n_hoods < h.n_hoods or n_regions < h.n_regions:
        raise ValueError(
            f"bucket ({capacity}, {n_hoods}, {n_regions}) smaller than hoods "
            f"({h.capacity}, {h.n_hoods}, {h.n_regions})"
        )
    if n_elements is None:
        n_elements = h.n_elements
    if (capacity, n_hoods, n_regions, n_elements) == (
        h.capacity, h.n_hoods, h.n_regions, h.n_elements,
    ):
        return h
    return _pad_hoods(
        h, capacity=capacity, n_hoods=n_hoods, n_regions=n_regions,
        n_elements=n_elements,
    )


@functools.partial(
    jax.jit, static_argnames=("capacity", "n_hoods", "n_regions", "n_elements")
)
def _pad_hoods(h: Hoods, *, capacity: int, n_hoods: int, n_regions: int,
               n_elements: int) -> Hoods:
    """The array work of :func:`pad_hoods`, one program per (slice, bucket)
    shape pair."""

    def pad1(x, fill, total):
        return jnp.full((total,), fill, x.dtype).at[: x.shape[0]].set(x)

    valid = pad1(h.valid, False, capacity)
    vertex = jnp.where(valid, pad1(h.vertex, 0, capacity), n_regions)
    hood_id = jnp.where(valid, pad1(h.hood_id, 0, capacity), n_hoods)
    sizes = pad1(h.sizes, 0, n_hoods)
    offsets = jnp.concatenate(
        [h.offsets, jnp.full((n_hoods - h.n_hoods,), h.offsets[-1], h.offsets.dtype)]
    )
    rep_valid = pad1(h.rep_valid, False, 2 * capacity)
    rep_old_index = jnp.where(
        rep_valid, pad1(h.rep_old_index, 0, 2 * capacity), capacity - 1
    ).astype(jnp.int32)
    rep_test_label = jnp.where(rep_valid, pad1(h.rep_test_label, 0, 2 * capacity), 0)
    rep_hood_id = jnp.where(
        rep_valid, pad1(h.rep_hood_id, 0, 2 * capacity), n_hoods
    ).astype(jnp.int32)

    return Hoods(
        vertex=vertex.astype(jnp.int32),
        hood_id=hood_id.astype(jnp.int32),
        valid=valid,
        sizes=sizes,
        offsets=offsets,
        n_hoods=n_hoods,
        n_regions=n_regions,
        n_elements=n_elements,
        rep_old_index=rep_old_index,
        rep_test_label=rep_test_label.astype(jnp.int32),
        rep_hood_id=rep_hood_id,
        rep_valid=rep_valid,
    )


def _build_replication(
    hood_id: jnp.ndarray,
    valid: jnp.ndarray,
    sizes: jnp.ndarray,
    hood_offsets: jnp.ndarray,
    n_hoods: int,
    h_pad: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper's testLabel / oldIndex / hoodId arrays of size 2*|hoods|.

    Layout per neighborhood h with size s and packed offset o:
    lanes [2o, 2o+s) replicate h's elements with testLabel=0 and lanes
    [2o+s, 2o+2s) with testLabel=1 — exactly the worked example in §3.2.2.

    Because the packed (valid-only) element order may differ from the padded
    storage order, oldIndex points into the *packed* order; we therefore
    also need the packed->padded map, folded in here so rep_old_index
    indexes the padded arrays directly.
    """
    # Packed position of each padded lane (exclusive scan of valid flags).
    vi = valid.astype(jnp.int32)
    packed_pos = (jnp.cumsum(vi) - vi).astype(jnp.int32)
    # padded index of each packed element:
    pad_of_packed = dpp.scatter_(
        jnp.arange(h_pad, dtype=jnp.int32), packed_pos, h_pad, mode="set",
        fill=h_pad - 1, mask=valid,
    )

    rep_counts = (2 * sizes).astype(jnp.int32)
    total = 2 * h_pad
    rep_hood, rep_rank = dpp.expand_with_rank(rep_counts, total)
    rep_lane_valid = rep_hood < n_hoods
    safe_hood = jnp.minimum(rep_hood, n_hoods - 1)
    s = sizes[safe_hood]
    o = hood_offsets[safe_hood]
    test_label = jnp.where(rep_rank >= s, 1, 0).astype(jnp.int32)
    packed_idx = o + jnp.where(rep_rank >= s, rep_rank - s, rep_rank)
    packed_idx = jnp.minimum(packed_idx, h_pad - 1)
    old_index = pad_of_packed[packed_idx]
    return (
        jnp.where(rep_lane_valid, old_index, h_pad - 1).astype(jnp.int32),
        jnp.where(rep_lane_valid, test_label, 0),
        jnp.where(rep_lane_valid, rep_hood, n_hoods).astype(jnp.int32),
        rep_lane_valid,
    )
