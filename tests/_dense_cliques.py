"""The dense maximal-clique enumerator, kept as a test oracle for
:func:`repro.core.pmrf.cliques.enumerate_maximal_cliques`.

This is the algorithm the program ran before it enumerated from the CSR:
a dense (regions x regions) adjacency, and at every clique size the AND of
the members' adjacency rows as (cliques x regions) boolean arrays.  The
tests hold the CSR enumerator to it member for member and row for row.
"""

from __future__ import annotations

import numpy as np

from repro.core.pmrf.cliques import CliqueSet


def dense_adjacency(graph) -> np.ndarray:
    """(n_regions, n_regions) bool adjacency, zero diagonal."""
    adj = np.zeros((graph.n_regions, graph.n_regions), dtype=bool)
    eu, ev = graph.edges[:, 0], graph.edges[:, 1]
    adj[eu, ev] = True
    adj[ev, eu] = True
    return adj


def dense_maximal_cliques(graph, max_size=None) -> CliqueSet:
    adj = dense_adjacency(graph)
    n = graph.n_regions
    if max_size is None:
        max_size = n
    maximal = []
    isolated = np.nonzero(adj.sum(axis=1) == 0)[0].astype(np.int32)
    if isolated.size:
        maximal.append(isolated[:, None])
    cliques = graph.edges.astype(np.int32)
    k = 2
    while cliques.size and k <= max_size:
        common = np.ones((cliques.shape[0], n), dtype=bool)
        for col in range(k):
            common &= adj[cliques[:, col]]
        is_max = ~common.any(axis=1)
        if is_max.any():
            maximal.append(cliques[is_max])
        ext = common & (np.arange(n)[None, :] > cliques[:, -1:])
        rows, cols = np.nonzero(ext)
        if rows.size == 0:
            break
        cliques = np.concatenate([cliques[rows], cols[:, None].astype(np.int32)], axis=1)
        k += 1
    if not maximal:
        return CliqueSet(members=np.zeros((0, 2), np.int32), sizes=np.zeros((0,), np.int32))
    width = max(c.shape[1] for c in maximal)
    out = np.full((sum(c.shape[0] for c in maximal), width), -1, dtype=np.int32)
    sizes = np.zeros((out.shape[0],), dtype=np.int32)
    r = 0
    for c in maximal:
        out[r : r + c.shape[0], : c.shape[1]] = c
        sizes[r : r + c.shape[0]] = c.shape[1]
        r += c.shape[0]
    return CliqueSet(members=out, sizes=sizes)
