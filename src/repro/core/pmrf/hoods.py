"""k=1 neighborhood construction from maximal cliques (paper §3.2.2).

Implements the paper's four data-parallel steps verbatim on top of the DPP
layer:

  1. **Find Neighbors** (Map): per clique-member slot, count 1-hop
     neighbors that are not members of the slot's clique.
  2. **Count Neighbors** (Scan): prefix-sum the counts to allocate the
     neighborhoods array.  The capacity is counted on the host and rounded
     up on the session's bucket grid, so the program compiles once per
     shape class, not per slice; the natural prefix is trimmed on the host
     (the XLA static-shape adaptation, DESIGN.md §2).
  3. **Get Neighbors** (Map): populate candidate (cliqueId, vertexId)
     elements via the expand idiom (Scatter + max-Scan + Gather).
  4. **Remove Duplicate Neighbors** (SortByKey + Unique): sort candidates
     by the (cliqueId, vertexId) pair, two keys, and drop adjacent
     duplicates.

It also builds the paper's label-replication index arrays (testLabel,
oldIndex, hoodId — the "repHoods" simulated, memory-free Gather), and on
the host the elements' vertex order: the runs the EM's label votes are
summed over (:func:`vote_order`).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import budget as budget_mod
from repro.core import dpp
from repro.core.pmrf.cliques import CliqueSet
from repro.core.pmrf.graph import RegionGraph

#: Granularity the padded neighborhood capacity is rounded up to.  Coarse
#: buckets mean slightly different problems share one compiled executable
#: (every static dim feeds the Hoods treedef, so an exact max would
#: recompile on a one-element difference).
DEFAULT_CAPACITY_BUCKET = 256
#: Granularity for the n_hoods / n_regions static dims.
DEFAULT_SEGMENT_BUCKET = 64

# Shape classes :func:`build_hoods` has run in this process; the first run
# of a class counts a ``plan.hood_class_miss``, every later one a hit.
_SEEN_CLASSES: set = set()


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
    # Elements in vertex order (:func:`vote_order`): ``vote_perm`` (H_pad,)
    # int32 is a stable argsort of ``vertex``, and the elements of region r
    # sit at sorted positions [vote_bounds[r], vote_bounds[r + 1]);
    # ``vote_bounds`` is (n_regions + 2,) int32.
    vote_perm: jnp.ndarray
    vote_bounds: jnp.ndarray

    @property
    def capacity(self) -> int:
        return int(self.vertex.shape[0])


def build_hoods(
    graph: RegionGraph,
    cliques: CliqueSet,
    *,
    capacity_bucket: int = DEFAULT_CAPACITY_BUCKET,
    segment_bucket: int = DEFAULT_SEGMENT_BUCKET,
) -> Hoods:
    """The k=1 neighbourhoods of ``cliques`` in ``graph``.

    The device work compiles at class shapes: the slice's counts rounded
    up on the session's bucket grid (``capacity_bucket`` for lanes,
    ``segment_bucket`` for clique rows), so slices whose natural shapes
    differ share one program.  The natural prefix is read back and kept on
    the host; the result is the same as a build at the natural shapes.
    """
    n = graph.n_regions
    c = cliques.n_cliques
    if c == 0:
        raise ValueError("no cliques — empty graph?")

    # Step 2's capacity, counted on the host from the graph: one candidate
    # per (clique member, 1-hop neighbor) pair; with the natural build's
    # C·W member lanes it gives the natural lane count, the capacity the
    # Hoods keep (every slice's own members fit in C·W lanes).
    members = cliques.members
    deg = np.diff(graph.csr_offsets)
    slot_clique, slot_col = np.nonzero(members >= 0)  # row-major member slots
    slot_member = members[slot_clique, slot_col]
    neighbor_capacity = int(deg[slot_member].sum())
    h_nat = neighbor_capacity + c * cliques.width

    # Class shapes: the clique count, the CSR neighbour array and the key
    # lanes rounded up on the bucket grid; the key lanes are the session's
    # bucket capacity.  The member slots (one per clique member, padded
    # with -1: no member, so no key) take the lane count too, so neither
    # the clique width nor the member count, which vary from slice to
    # slice, is a dimension of the class.
    c_pad = _round_up(c, segment_bucket)
    neighbors_pad = _padded(graph.csr_neighbors, _round_up(
        max(graph.csr_neighbors.shape[0], 1), capacity_bucket), 0)
    n_lanes = _round_up(h_nat, capacity_bucket)
    shape_class = (c_pad, neighbors_pad.shape[0], n, n_lanes)
    hit = shape_class in _SEEN_CLASSES
    _SEEN_CLASSES.add(shape_class)
    budget_mod.LEDGER.bump("plan", "hood_class_hit" if hit else "hood_class_miss")

    out = _hood_arrays(
        jnp.asarray(_padded(slot_member, n_lanes, -1)),
        jnp.asarray(_padded(slot_clique.astype(np.int32), n_lanes, c_pad)),
        jnp.asarray(graph.csr_offsets),
        jnp.asarray(neighbors_pad),
        np.int32(c),
        np.int32(h_nat),
        n_cliques=c_pad,
        n_regions=n,
        n_lanes=n_lanes,
    )
    # Trim to the natural prefix on the host: a device slice at the
    # slice's own sizes would compile per slice again.
    vertex, hood_id, valid, sizes, offsets, rep = jax.device_get(out)
    n_elements = int(valid[:h_nat].sum())
    vertex = vertex[:h_nat]
    vote_perm, vote_bounds = vote_order(vertex, n)
    (vertex, hood_id, valid, sizes, offsets, rep, vote_perm,
     vote_bounds) = jax.device_put((
        vertex, hood_id[:h_nat], valid[:h_nat], sizes[:c], offsets[: c + 1],
        tuple(r[: 2 * h_nat] for r in rep), vote_perm, vote_bounds,
    ))
    return Hoods(
        vertex=vertex,
        hood_id=hood_id,
        valid=valid,
        sizes=sizes,
        offsets=offsets,
        n_hoods=c,
        n_regions=n,
        n_elements=n_elements,
        rep_old_index=rep[0],
        rep_test_label=rep[1],
        rep_hood_id=rep[2],
        rep_valid=rep[3],
        vote_perm=vote_perm,
        vote_bounds=vote_bounds,
    )


def vote_order(vertex: np.ndarray, n_regions: int) -> Tuple[np.ndarray, np.ndarray]:
    """The elements in vertex order, on the host: ``(perm, bounds)`` with
    ``perm`` a stable argsort of ``vertex`` (values in [0, n_regions], the
    sentinel included) and ``bounds[k]`` the number of elements whose
    vertex is below ``k``, for k in [0, n_regions + 1].

    A least-significant-digit radix sort over 16-bit digits: NumPy sorts
    16-bit keys stably by counting, several times faster than a comparison
    sort of the 2.4M elements of a full slice.
    """
    vertex = np.asarray(vertex, np.int32)
    perm = np.arange(vertex.shape[0])
    for shift in range(0, max(int(n_regions).bit_length(), 1), 16):
        digit = ((vertex[perm] >> shift) & 0xFFFF).astype(np.uint16)
        perm = perm[np.argsort(digit, kind="stable")]
    counts = np.bincount(vertex, minlength=n_regions + 1)
    bounds = np.concatenate([[0], np.cumsum(counts)])
    return perm.astype(np.int32), bounds.astype(np.int32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded(x: np.ndarray, total: int, fill) -> np.ndarray:
    out = np.full((total,), fill, x.dtype)
    out[: x.shape[0]] = x
    return out


@functools.partial(jax.jit, static_argnames=("n_cliques", "n_regions", "n_lanes"))
def _hood_arrays(
    slot_member, slot_clique, offsets, neighbors, c, h_nat, *,
    n_cliques: int, n_regions: int, n_lanes: int,
):
    """Steps 1, 3 and 4 plus the label replication, for :func:`build_hoods`.

    Shapes are the class's: ``slot_member``/``slot_clique`` hold each
    clique member and its clique in their first lanes (member -1 in the
    rest), there are ``n_cliques`` clique ids (the real ``c`` and padding),
    ``neighbors`` is padded, and there are ``n_lanes`` key lanes.  The
    natural counts ``c`` and ``h_nat`` are traced, and every value that
    depended on them is computed from them, so the first ``h_nat`` lanes
    (``2 * h_nat`` replication lanes, ``c`` sizes, ``c + 1`` offsets) equal
    a build at the natural shapes.
    """
    n = n_regions
    c_pad = n_cliques
    valid_slot = slot_member >= 0
    deg = offsets[1:] - offsets[:-1]
    safe_member = jnp.where(valid_slot, slot_member, 0)

    # -- Step 1: Find Neighbors (Map) — per-slot element counts: the member
    # itself and its 1-hop neighbors. --------------------------------------
    slot_counts = jnp.where(valid_slot, deg[safe_member] + 1, 0).astype(jnp.int32)

    # -- Step 3: Get Neighbors (Map over expanded lanes). ------------------
    # Each slot's lanes hold its member (rank 0), then the member's CSR row;
    # lanes past the slots' total count fall outside every slot (invalid).
    src_slot, rank = dpp.expand_with_rank(slot_counts, n_lanes)
    lane_valid = src_slot < n_lanes
    safe_slot = jnp.minimum(src_slot, n_lanes - 1)
    v = safe_member[safe_slot]
    nb = neighbors[jnp.clip(offsets[v] + rank - 1, 0, neighbors.shape[0] - 1)]
    element = jnp.where(rank == 0, v, nb)

    # Hood = clique U 1-hop neighbors.  Every lane is a (cliqueId, vertexId)
    # pair; lanes that hold no element carry the sentinel (c_pad, n), above
    # every real pair as the natural build's (c, n) is, so real pairs sort
    # the same.  A neighbor that is a member of the same clique repeats that
    # member's pair, and Unique drops it (the paper filters it out in step
    # 1).  The pair is sorted as two keys, never packed into one integer:
    # (C_pad + 1)(n + 1) passes int32 on a full 1813x1830 slice.
    key_cid = jnp.where(lane_valid, slot_clique[safe_slot], c_pad)
    key_v = jnp.where(lane_valid, element, n)

    # -- Step 4: Remove Duplicate Neighbors (SortByKey + Unique). ----------
    # Only the keys are sorted, so equal lanes are interchangeable.
    sorted_keys = dpp.sort_by_key((key_cid, key_v), num_keys=2, stable=False)
    # Padding lanes of unique_ carry the sentinel, which a surviving
    # sentinel pair also is: a lane is an element iff its clique is real.
    (hood_id, vertex), _ = dpp.unique_(sorted_keys, fill=(c_pad, n))
    valid = hood_id != c_pad

    sizes = dpp.reduce_by_key(
        hood_id,
        valid.astype(jnp.int32),
        c_pad + 1,
        op="add",
    )[:c_pad]
    hood_offsets = dpp.counts_to_offsets(sizes)

    # -- Replication by label (paper: Map + Scan + Gather, memory-free). ---
    rep = _build_replication(valid, sizes, hood_offsets, c, h_nat)
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
    # Every lane past the slice's sorts with the invalid ones after the
    # valid elements (the bucket's sentinel vertex), in lane order.
    vote_perm = jnp.concatenate(
        [h.vote_perm, jnp.arange(h.capacity, capacity, dtype=jnp.int32)]
    )
    n_valid = h.vote_bounds[h.n_regions]
    vote_bounds = jnp.concatenate([
        h.vote_bounds[: h.n_regions + 1],
        jnp.full((n_regions - h.n_regions,), n_valid, jnp.int32),
        jnp.full((1,), capacity, jnp.int32),
    ])

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
        vote_perm=vote_perm.astype(jnp.int32),
        vote_bounds=vote_bounds.astype(jnp.int32),
    )


def _build_replication(
    valid: jnp.ndarray,
    sizes: jnp.ndarray,
    hood_offsets: jnp.ndarray,
    n_hoods: jnp.ndarray,
    h_nat: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper's testLabel / oldIndex / hoodId arrays of size 2*|hoods|.

    Layout per neighborhood h with size s and packed offset o:
    lanes [2o, 2o+s) replicate h's elements with testLabel=0 and lanes
    [2o+s, 2o+2s) with testLabel=1 — exactly the worked example in §3.2.2.

    Because the packed (valid-only) element order may differ from the padded
    storage order, oldIndex points into the *packed* order; we therefore
    also need the packed->padded map, folded in here so rep_old_index
    indexes the padded arrays directly.

    ``n_hoods`` and ``h_nat`` (the natural hood count and lane count) are
    traced; lanes and hoods past them are padding of the class shape, and
    fills and clamps use the natural counts.
    """
    n_lanes = valid.shape[0]
    # Packed position of each padded lane (exclusive scan of valid flags).
    vi = valid.astype(jnp.int32)
    packed_pos = dpp.scan_(vi, exclusive=True)
    # padded index of each packed element:
    pad_of_packed = dpp.scatter_(
        jnp.arange(n_lanes, dtype=jnp.int32), packed_pos, n_lanes, mode="set",
        fill=h_nat - 1, mask=valid,
    )

    rep_counts = (2 * sizes).astype(jnp.int32)
    rep_hood, rep_rank = dpp.expand_with_rank(rep_counts, 2 * n_lanes)
    rep_lane_valid = rep_hood < n_hoods
    safe_hood = jnp.minimum(rep_hood, n_hoods - 1)
    s = sizes[safe_hood]
    o = hood_offsets[safe_hood]
    test_label = jnp.where(rep_rank >= s, 1, 0).astype(jnp.int32)
    packed_idx = o + jnp.where(rep_rank >= s, rep_rank - s, rep_rank)
    packed_idx = jnp.minimum(packed_idx, h_nat - 1)
    old_index = pad_of_packed[packed_idx]
    return (
        jnp.where(rep_lane_valid, old_index, h_nat - 1).astype(jnp.int32),
        jnp.where(rep_lane_valid, test_label, 0),
        jnp.where(rep_lane_valid, rep_hood, n_hoods).astype(jnp.int32),
        rep_lane_valid,
    )
