"""Maximal clique enumeration for region-adjacency graphs (paper §3.2.1).

The paper builds MRF neighborhoods on top of the maximal cliques of the
RAG, enumerated with the DPP-based MCE of Lessley et al. [23].  Key
structural fact we exploit on TPU: the RAG of a 2D oversegmentation is
mostly planar, so maximal cliques are small (<= 4 for strictly planar
graphs; spatially fragmented superpixels create occasional denser pockets,
which the enumerator handles by simply iterating deeper).  We enumerate by
canonical extension —
each clique is grown only by vertices larger than its current maximum, so
every k-clique is generated exactly once through its sorted prefix chain —
and emit a clique when its common-neighbor set is empty (the maximality
test).  The iteration depth equals the largest clique size (3-5 here), and
every level is one vectorized pass over the graph's CSR: each clique's
candidates are the neighbors of its last member (Map + Expand), each
candidate is tested for adjacency to the other members by binary search
of the sorted edge keys, and a clique with no candidate adjacent to every
member is maximal.  Work and memory are linear in the candidates, never
(cliques x regions).

Runs in the initialization phase (untimed in the paper's methodology);
implemented in numpy on the host.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from repro.analysis import budget as budget_mod
from repro.core.pmrf.graph import RegionGraph


@dataclass
class CliqueSet:
    """Maximal cliques, padded to ``width`` with -1."""

    members: np.ndarray  # (n_cliques, width) int32, rows sorted ascending
    sizes: np.ndarray    # (n_cliques,) int32

    @property
    def n_cliques(self) -> int:
        return int(self.members.shape[0])

    @property
    def width(self) -> int:
        return int(self.members.shape[1])


def _row_of(graph: RegionGraph, vertices: np.ndarray):
    """The CSR rows of ``vertices``, concatenated: (owner index, neighbor)
    per entry, in row order and ascending within each row."""
    start = graph.csr_offsets[vertices].astype(np.int64)
    counts = graph.csr_offsets[vertices + 1] - start
    owner = np.repeat(np.arange(vertices.shape[0]), counts)
    first = np.cumsum(counts) - counts
    idx = np.arange(owner.shape[0]) + np.repeat(start - first, counts)
    return owner, graph.csr_neighbors[idx]


def enumerate_maximal_cliques(
    graph: RegionGraph, max_size: int | None = None, max_frontier: int = 2_000_000
) -> CliqueSet:
    """Every maximal clique: isolated vertices first, then by size, then
    lexicographically.  Counts the candidates tested in ``LEDGER``
    ``plan.clique_candidates``."""
    n = graph.n_regions
    if max_size is None:
        max_size = n  # loop to exhaustion; RAG clique number is small

    maximal: List[np.ndarray] = []  # list of (m_k, k) arrays

    # Level 1: isolated vertices are maximal 1-cliques.
    isolated = np.nonzero(graph.degree() == 0)[0].astype(np.int32)
    if isolated.size:
        maximal.append(isolated[:, None])

    # Level 2 seeds: all edges (u < v), in key order.
    cliques = graph.edges.astype(np.int32)  # (m, 2)

    k = 2
    while cliques.size and k <= max_size:
        # A common neighbor of every member is a neighbor of the last one:
        # test each of its neighbors against the other members (a member
        # is never adjacent to itself, so members drop out).
        owner, cand = _row_of(graph, cliques[:, -1])
        budget_mod.LEDGER.bump("plan", "clique_candidates", int(cand.shape[0]))
        common = np.ones(cand.shape, bool)
        for col in range(k - 1):
            common &= graph.adjacent(cliques[owner, col], cand)
        has_common = np.zeros(cliques.shape[0], bool)
        has_common[owner[common]] = True
        if not has_common.all():
            maximal.append(cliques[~has_common])

        # Canonical extension: only w > max(member ids) = last column.
        ext = common & (cand > cliques[owner, -1])
        rows, cols = owner[ext], cand[ext]
        if rows.size == 0:
            break
        if rows.size > max_frontier:
            raise RuntimeError(
                f"clique frontier exploded ({rows.size}) — graph is far from "
                "planar; check the oversegmentation"
            )
        cliques = np.concatenate(
            [cliques[rows], cols[:, None].astype(np.int32)], axis=1
        )
        k += 1

    if not maximal:
        return CliqueSet(
            members=np.zeros((0, 2), np.int32), sizes=np.zeros((0,), np.int32)
        )

    width = max(c.shape[1] for c in maximal)
    rows = sum(c.shape[0] for c in maximal)
    out = np.full((rows, width), -1, dtype=np.int32)
    sizes = np.zeros((rows,), dtype=np.int32)
    r = 0
    for c in maximal:
        out[r : r + c.shape[0], : c.shape[1]] = c
        sizes[r : r + c.shape[0]] = c.shape[1]
        r += c.shape[0]
    return CliqueSet(members=out, sizes=sizes)


def verify_maximal_cliques(graph: RegionGraph, cliques: CliqueSet) -> bool:
    """Oracle check used by tests: every row is a clique, and no row can be
    extended by any vertex (maximality)."""
    for row, size in zip(cliques.members, cliques.sizes):
        mem = row[:size]
        i, j = np.triu_indices(size, 1)
        if not graph.adjacent(mem[i], mem[j]).all():
            return False
        _, cand = _row_of(graph, mem[:1])
        common = np.ones(cand.shape, bool)
        for v in mem:
            common &= graph.adjacent(np.full(cand.shape, v), cand)
        if common.any():
            return False
    return True
