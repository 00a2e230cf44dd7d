"""The neighbourhood builder at each slice's natural shapes, kept as a test
oracle for :func:`repro.core.pmrf.hoods.build_hoods`.

This is the algorithm ``build_hoods`` ran before it compiled at class
shapes: one jitted program per natural ``(C, W)`` clique matrix, CSR
neighbour length and neighbour capacity, every count static.  The tests
hold the class-shaped builder to it field for field.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dpp
from repro.core.pmrf.hoods import Hoods, vote_order


def natural_hoods(graph, cliques) -> Hoods:
    n = graph.n_regions
    c = cliques.n_cliques
    members = cliques.members
    deg = np.diff(graph.csr_offsets)
    neighbor_capacity = int(deg[members[members >= 0]].sum())
    vertex, hood_id, valid, sizes, hood_offsets, rep = _natural_arrays(
        jnp.asarray(members),
        jnp.asarray(graph.csr_offsets),
        jnp.asarray(graph.csr_neighbors),
        n_regions=n,
        neighbor_capacity=neighbor_capacity,
    )
    perm, bounds = vote_order(np.asarray(vertex), n)
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
        vote_perm=jnp.asarray(perm),
        vote_bounds=jnp.asarray(bounds),
    )


@functools.partial(jax.jit, static_argnames=("n_regions", "neighbor_capacity"))
def _natural_arrays(members, offsets, neighbors, *, n_regions, neighbor_capacity):
    n = n_regions
    c, w = members.shape
    members_flat = members.reshape(-1)
    clique_of_slot = jnp.repeat(jnp.arange(c, dtype=jnp.int32), w)
    valid_slot = members_flat >= 0
    n_slots = c * w
    deg = offsets[1:] - offsets[:-1]
    safe_member = jnp.where(valid_slot, members_flat, 0)
    slot_counts = jnp.where(valid_slot, deg[safe_member], 0).astype(jnp.int32)

    src_slot, rank = dpp.expand_with_rank(slot_counts, neighbor_capacity)
    lane_valid = src_slot < n_slots
    safe_slot = jnp.minimum(src_slot, n_slots - 1)
    v = safe_member[safe_slot]
    nb = neighbors[jnp.minimum(offsets[v] + rank, neighbors.shape[0] - 1)]
    cid = clique_of_slot[safe_slot]
    nb_in_clique = jnp.any(members[cid] == nb[:, None], axis=1)
    cand_valid_nb = lane_valid & ~nb_in_clique

    span = n + 1
    sentinel = c * span + n
    key_nb = jnp.where(
        cand_valid_nb, dpp.compound_key(cid, nb, span, major_span=c + 1), sentinel
    )
    key_mem = jnp.where(
        valid_slot,
        dpp.compound_key(clique_of_slot, safe_member, span, major_span=c + 1),
        sentinel,
    )
    keys = jnp.concatenate([key_mem, key_nb])

    (sorted_keys,) = dpp.sort_by_key(keys)
    uniq, count = dpp.unique_(sorted_keys, fill=sentinel)
    lane = jnp.arange(uniq.shape[0])
    uniq = jnp.where((lane < count) & (uniq != sentinel), uniq, sentinel)

    hood_id = (uniq // span).astype(jnp.int32)
    vertex = (uniq % span).astype(jnp.int32)
    valid = uniq != sentinel
    sizes = dpp.reduce_by_key(
        jnp.where(valid, hood_id, c), valid.astype(jnp.int32), c + 1, op="add"
    )[:c]
    hood_offsets = dpp.counts_to_offsets(sizes)

    h_pad = int(vertex.shape[0])
    rep = _natural_replication(valid, sizes, hood_offsets, c, h_pad)
    return vertex, jnp.where(valid, hood_id, c), valid, sizes, hood_offsets, rep


def _natural_replication(valid, sizes, hood_offsets, n_hoods, h_pad):
    vi = valid.astype(jnp.int32)
    packed_pos = (jnp.cumsum(vi) - vi).astype(jnp.int32)
    pad_of_packed = dpp.scatter_(
        jnp.arange(h_pad, dtype=jnp.int32), packed_pos, h_pad, mode="set",
        fill=h_pad - 1, mask=valid,
    )
    rep_counts = (2 * sizes).astype(jnp.int32)
    rep_hood, rep_rank = dpp.expand_with_rank(rep_counts, 2 * h_pad)
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
