"""Region-adjacency graph construction from an oversegmentation (paper §3.2.1).

Each vertex is an oversegmented region; an edge connects regions whose
pixels share a boundary.  Region statistics (mean intensity = the MRF data
term source, pixel counts = M-step weights) are computed with ReduceByKey
over the pixel label map.  The graph is stored in CSR form (the paper's
compressed sparse row representation, following [23]) next to its sorted
edge keys, which answer "are u and v adjacent" by binary search: nothing
is (regions x regions), so a full 1813x1830 slice at ~52k regions costs
memory in its edges alone.

Construction runs in the *initialization* phase (the paper times only the
optimization loop), so host-side numpy is used where it is clearer;
reductions over pixels use the DPP layer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import dpp


@dataclass
class RegionGraph:
    """CSR + sorted edge keys + per-region statistics."""

    n_regions: int
    edges: np.ndarray          # (E, 2) int32, u < v, deduped, sorted
    edge_keys: np.ndarray      # (E,) int64 u * n_regions + v, ascending
    csr_offsets: np.ndarray    # (n_regions + 1,) int32
    csr_neighbors: np.ndarray  # (2E,) int32, ascending within each row
    region_mean: np.ndarray    # (n_regions,) float32 — MRF data term
    region_size: np.ndarray    # (n_regions,) float32 — pixel counts

    @property
    def n_edges(self) -> int:
        return int(self.edges.shape[0])

    def degree(self) -> np.ndarray:
        return np.diff(self.csr_offsets)

    @classmethod
    def from_edge_keys(cls, key: np.ndarray, n_regions: int, region_mean,
                       region_size) -> "RegionGraph":
        """The graph of the unique, ascending edge keys ``u * n_regions + v``
        (``u < v``): the CSR holds both directions of every edge, sorted by
        (row, column)."""
        key = np.asarray(key, np.int64)
        eu, ev = key // n_regions, key % n_regions
        arcs = np.sort(np.concatenate([key, ev * n_regions + eu]))
        deg = np.bincount(arcs // n_regions, minlength=n_regions).astype(np.int32)
        offsets = np.zeros(n_regions + 1, dtype=np.int32)
        np.cumsum(deg, out=offsets[1:])
        return cls(
            n_regions=n_regions,
            edges=np.stack([eu, ev], axis=1).astype(np.int32),
            edge_keys=key,
            csr_offsets=offsets,
            csr_neighbors=(arcs % n_regions).astype(np.int32),
            region_mean=region_mean,
            region_size=region_size,
        )

    def adjacent(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Elementwise: is there an edge between ``u`` and ``v``?  A binary
        search of the sorted edge keys; ``u == v`` is never an edge."""
        u, v = np.asarray(u, np.int64), np.asarray(v, np.int64)
        key = np.minimum(u, v) * self.n_regions + np.maximum(u, v)
        pos = np.searchsorted(self.edge_keys, key)
        inside = pos < self.n_edges
        found = np.zeros(key.shape, bool)
        found[inside] = self.edge_keys[pos[inside]] == key[inside]
        return found


def region_stats(image, labels, n_regions: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-region mean intensity + pixel count via ReduceByKey."""
    flat_img = jnp.asarray(image).ravel().astype(jnp.float32)
    flat_lab = jnp.asarray(labels).ravel().astype(jnp.int32)
    sums = dpp.reduce_by_key(flat_lab, flat_img, n_regions, op="add")
    counts = dpp.reduce_by_key(
        flat_lab, jnp.ones_like(flat_img), n_regions, op="add"
    )
    means = sums / jnp.maximum(counts, 1.0)
    return np.asarray(means, np.float32), np.asarray(counts, np.float32)


def build_region_graph(image, labels, n_regions: int) -> RegionGraph:
    """Build the RAG from a pixel label map.

    Boundary detection is a Map over horizontal/vertical pixel pairs; edge
    deduplication is SortByKey + Unique (done in numpy on the host — this is
    init-phase code, see module docstring).
    """
    lab = np.asarray(labels).astype(np.int64)

    pairs_h = np.stack([lab[:, :-1].ravel(), lab[:, 1:].ravel()], axis=1)
    pairs_v = np.stack([lab[:-1, :].ravel(), lab[1:, :].ravel()], axis=1)
    pairs = np.concatenate([pairs_h, pairs_v], axis=0)
    diff = pairs[:, 0] != pairs[:, 1]
    pairs = pairs[diff]
    u = np.minimum(pairs[:, 0], pairs[:, 1])
    v = np.maximum(pairs[:, 0], pairs[:, 1])
    key = u * n_regions + v
    key = np.unique(key)  # SortByKey + Unique
    mean, size = region_stats(image, labels, n_regions)
    return RegionGraph.from_edge_keys(key, n_regions, mean, size)
