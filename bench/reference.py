"""The plain reference that decides ``correct``: image in, segmentation out.

It imports nothing of the program and takes nothing the program has made.
Every stage follows the method the configuration names (arXiv 1809.05018
§3.2 with the repository's SLIC oversegmentation and quantile init):

1. SLIC superpixels: grid-seeded k-means over (y, x, smoothed intensity),
   dense assignment to every seed (JAX, run on the device in pixel blocks);
2. the region graph: 4-neighbour pixel pairs of different regions, region
   mean intensity and pixel count (NumPy, float64 sums rounded to float32);
3. maximal cliques by Bron-Kerbosch with pivoting, ordered by size, then
   lexicographically;
4. neighbourhoods: each clique's members and their 1-hop neighbours;
5. the energy model and quantile init;
6. EM/MAP: a copy of the program's NumPy golden oracle
   (``reference.golden_em``), float32 with element-order sums.

``dtype="bf16"`` computes every stage in bfloat16: the control that the
comparison must refuse (``bench/control.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

WINDOW = 3
CONV_TOL = 1.0e-4
DTYPES = {"f32": (np.float32, jnp.float32), "bf16": (ml_dtypes.bfloat16, jnp.bfloat16)}


@dataclass
class RefSegmentation:
    segmentation: np.ndarray   # (H, W) int32
    region_labels: np.ndarray  # (n_regions,) int32
    superpixels: np.ndarray    # (H, W) int32 SLIC labels
    mu: np.ndarray
    sigma: np.ndarray
    total_energy: float
    em_iters: int
    map_iters: int
    n_hoods: int
    n_elements: int


# ---------------------------------------------------------------------------
# 1. SLIC
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("grid", "iters", "dtype", "blocks"))
def slic(image, *, grid, iters, dtype=jnp.float32, blocks=16, compactness=0.5):
    """(H, W) int32 superpixel labels in [0, gy*gx).  The pixel-to-seed
    distances are taken in ``blocks`` row blocks so the dense (pixels x
    seeds) array never exists whole."""
    h, w = image.shape
    gy, gx = grid
    k = gy * gx
    image = image.astype(dtype)
    pad = jnp.pad(image, 1, mode="edge")
    sm = (
        pad[:-2, :-2] + pad[:-2, 1:-1] + pad[:-2, 2:]
        + pad[1:-1, :-2] + pad[1:-1, 1:-1] + pad[1:-1, 2:]
        + pad[2:, :-2] + pad[2:, 1:-1] + pad[2:, 2:]
    ) / 9.0
    img = (sm - jnp.mean(sm)) / (jnp.std(sm) + 1e-6)
    ys = (jnp.arange(gy, dtype=dtype) + 0.5) * (h / gy)
    xs = (jnp.arange(gx, dtype=dtype) + 0.5) * (w / gx)
    cy, cx = jnp.meshgrid(ys, xs, indexing="ij")
    step = max(h / gy, w / gx)
    fy = (jnp.arange(h, dtype=dtype)[:, None] * jnp.ones((1, w), dtype)).ravel()
    fx = (jnp.ones((h, 1), dtype) * jnp.arange(w, dtype=dtype)[None, :]).ravel()
    fi = img.ravel()
    c_y, c_x = cy.ravel(), cx.ravel()
    c_i = img[jnp.clip(c_y.astype(jnp.int32), 0, h - 1), jnp.clip(c_x.astype(jnp.int32), 0, w - 1)]

    def assign(c_y, c_x, c_i):
        def block(args):
            by, bx, bi = args
            dy = by[:, None] - c_y[None, :]
            dx = bx[:, None] - c_x[None, :]
            di = bi[:, None] - c_i[None, :]
            d = compactness * (dy * dy + dx * dx) / (step * step) + di * di
            return jnp.argmin(d, axis=1).astype(jnp.int32)

        parts = (fy.reshape(blocks, -1), fx.reshape(blocks, -1), fi.reshape(blocks, -1))
        return jax.lax.map(block, parts).ravel()

    def body(_, c):
        c_y, c_x, c_i = c
        lab = assign(c_y, c_x, c_i)
        cnt = jax.ops.segment_sum(jnp.ones_like(fi), lab, num_segments=k)
        safe = jnp.maximum(cnt, 1.0)
        upd = lambda f, old: jnp.where(cnt > 0, jax.ops.segment_sum(f, lab, num_segments=k) / safe, old)
        return upd(fy, c_y), upd(fx, c_x), upd(fi, c_i)

    c = jax.lax.fori_loop(0, iters, body, (c_y, c_x, c_i))
    return assign(*c).reshape(h, w)


# ---------------------------------------------------------------------------
# 2-4. region graph, maximal cliques, neighbourhoods
# ---------------------------------------------------------------------------


def region_graph(image, labels, n_regions: int):
    """(neighbour sets, region means, region pixel counts)."""
    lab = np.asarray(labels, np.int64)
    pairs = np.concatenate([
        np.stack([lab[:, :-1].ravel(), lab[:, 1:].ravel()], 1),
        np.stack([lab[:-1, :].ravel(), lab[1:, :].ravel()], 1),
    ])
    pairs = np.unique(np.sort(pairs[pairs[:, 0] != pairs[:, 1]], axis=1), axis=0)
    nbrs = [set() for _ in range(n_regions)]
    for u, v in pairs:
        nbrs[u].add(int(v))
        nbrs[v].add(int(u))
    flat = lab.ravel()
    sums = np.bincount(flat, np.asarray(image, np.float64).ravel(), minlength=n_regions)
    sizes = np.bincount(flat, minlength=n_regions).astype(np.float64)
    means = sums / np.maximum(sizes, 1.0)
    return nbrs, means, sizes


def maximal_cliques(nbrs):
    """Every maximal clique (isolated vertices included) as a sorted tuple,
    ordered by size, then lexicographically.  Bron-Kerbosch with pivoting,
    started once per vertex on its later neighbours (Eppstein's ordering)."""
    out = []

    def expand(r, p, x):
        if not p and not x:
            out.append(tuple(sorted(r)))
            return
        pivot = max(p | x, key=lambda u: len(nbrs[u] & p))
        for v in list(p - nbrs[pivot]):
            expand(r | {v}, p & nbrs[v], x & nbrs[v])
            p = p - {v}
            x = x | {v}

    for v, nv in enumerate(nbrs):
        expand({v}, {u for u in nv if u > v}, {u for u in nv if u < v})
    return sorted(out, key=lambda c: (len(c), c))


def neighbourhoods(cliques, nbrs):
    """Flat (vertex, hood id) element arrays: hood h holds clique h's members
    and their 1-hop neighbours, in ascending vertex order."""
    vertex, hood = [], []
    for h, c in enumerate(cliques):
        members = set(c)
        for m in c:
            members |= nbrs[m]
        vs = sorted(members)
        vertex.extend(vs)
        hood.extend([h] * len(vs))
    return np.asarray(vertex, np.int32), np.asarray(hood, np.int32)


# ---------------------------------------------------------------------------
# 5. energy model and init
# ---------------------------------------------------------------------------


def energy_model(means, sizes, *, beta, sigma_min, n_labels, f):
    y = means.astype(f)
    w = (sizes / max(sizes.mean(), 1e-6)).astype(f)
    return dict(
        region_mean=np.concatenate([y, np.zeros(1, f)]),
        region_weight=np.concatenate([w, np.zeros(1, f)]),
        beta=f(beta), sigma_min=f(sigma_min),
        reseed_mu=np.quantile(y.astype(np.float64), np.linspace(0.10, 0.90, n_labels)).astype(f),
        reseed_sigma=f(max(y.astype(np.float64).std() / 2.0, sigma_min)),
    )


def quantile_init(means, n_labels, f):
    y = means.astype(f)
    mu = np.quantile(y.astype(np.float64), np.linspace(0.25, 0.75, n_labels)).astype(f)
    sigma = np.full(n_labels, y.astype(np.float64).std() / 2.0 + 1.0).astype(f)
    labels = np.argmin(np.abs(y[:, None] - mu[None, :]), axis=1).astype(np.int32)
    return np.concatenate([labels, np.zeros(1, np.int32)]), mu, sigma


# ---------------------------------------------------------------------------
# 6. EM / MAP (copy of the program's golden oracle)
# ---------------------------------------------------------------------------


def golden_em(vertex, hood_id, n_hoods, n_regions, model, labels0, mu0, sigma0,
              *, max_em_iters, max_map_iters, f=np.float32):
    """The static-mode EM driver in NumPy, precision ``f``; every element is
    valid (no padding lanes).  Returns (labels, mu, sigma, hood energies,
    em iterations, map iterations)."""
    nh, nr = n_hoods, n_regions
    y_all = np.asarray(model["region_mean"], f)
    w_all = np.asarray(model["region_weight"], f)
    beta, sig_min = f(model["beta"]), f(model["sigma_min"])
    reseed_mu, reseed_sigma = np.asarray(model["reseed_mu"], f), f(model["reseed_sigma"])
    K = int(np.asarray(mu0).shape[0])
    labels = np.asarray(labels0, np.int32).copy()
    mu, sigma = np.asarray(mu0, f).copy(), np.asarray(sigma0, f).copy()

    one = np.ones(len(vertex), f)
    y, w = y_all[vertex], w_all[vertex]
    nall = np.zeros(nh, f)
    np.add.at(nall, hood_id, one)
    nall_e = nall[hood_id]
    denom = np.maximum(nall_e - f(1.0), f(1.0))

    em_iters = map_total = 0
    hood_e = np.zeros(nh, f)
    total_hist = np.zeros(WINDOW + 1, f)
    for em in range(max_em_iters):
        em_iters += 1
        hist = np.zeros((WINDOW + 1, nh), f)
        for it in range(max_map_iters):
            map_total += 1
            x = labels[vertex]
            sig = np.maximum(sigma, sig_min)
            logsig = np.log(sig.astype(np.float64)).astype(f)
            cnt = np.zeros(nh * K, f)
            np.add.at(cnt, hood_id * K + x, one)
            cnt = cnt.reshape(nh, K)
            es = []
            for l in range(K):
                d = y - mu[l]
                data = w * (d * d / (f(2.0) * sig[l] * sig[l]) + logsig[l])
                diff = (nall_e - cnt[hood_id, l]) - (f(1.0) - (x == l).astype(f))
                es.append(data + beta * np.maximum(diff, f(0.0)) / denom)
            energies = np.stack(es)
            min_e, arg = energies.min(axis=0), energies.argmin(axis=0)
            hood_e = np.zeros(nh, f)
            np.add.at(hood_e, hood_id, min_e)
            votes = np.zeros((nr + 1) * K, f)
            np.add.at(votes, vertex * K + arg, one)
            labels = votes.reshape(nr + 1, K).argmax(axis=1).astype(np.int32)
            labels[nr] = 0
            hist = np.roll(hist, 1, axis=0)
            hist[0] = hood_e
            if it + 1 > WINDOW:
                scale = np.maximum(np.abs(hist[0]), f(1.0))
                if (np.abs(hist[:-1] - hist[1:]) < f(CONV_TOL) * scale).all():
                    break
        sw, swy, swyy = np.zeros(K, f), np.zeros(K, f), np.zeros(K, f)
        np.add.at(sw, labels, w_all)
        np.add.at(swy, labels, w_all * y_all)
        np.add.at(swyy, labels, w_all * y_all * y_all)
        safe = np.maximum(sw, f(1e-6))
        mu_n = swy / safe
        var = np.maximum(
            ((swyy / safe).astype(np.float64) - mu_n.astype(np.float64) ** 2).astype(f), f(0.0)
        )
        sigma_n = np.maximum(np.sqrt(var), sig_min)
        dead = sw < f(1e-3) * sw.sum(dtype=f)
        mu = np.where(dead, reseed_mu, mu_n).astype(f)
        sigma = np.where(dead, reseed_sigma, sigma_n).astype(f)
        total_hist = np.roll(total_hist, 1)
        total_hist[0] = hood_e.sum(dtype=f)
        if em + 1 > WINDOW:
            scale = np.maximum(np.abs(total_hist[0]), f(1.0))
            if (np.abs(total_hist[:-1] - total_hist[1:]) < f(CONV_TOL) * scale).all():
                break
    return labels[:nr], mu, sigma, hood_e, em_iters, map_total


# ---------------------------------------------------------------------------
# the whole pipeline
# ---------------------------------------------------------------------------


def segment(image, cfg: dict, dtype: str = "f32") -> RefSegmentation:
    """Segment one slice as the configuration states it."""
    f, jf = DTYPES[dtype]
    grid = tuple(cfg["overseg_grid"])
    n_regions = grid[0] * grid[1]
    h, _ = np.shape(image)
    blocks = next(b for b in (16, 8, 4, 2, 1) if h % b == 0)
    sp = np.asarray(slic(jnp.asarray(image, jnp.float32), grid=grid,
                         iters=cfg["overseg_iters"], dtype=jf, blocks=blocks))
    img = np.asarray(image, np.float32).astype(f)
    nbrs, means, sizes = region_graph(img, sp, n_regions)
    cliques = maximal_cliques(nbrs)
    vertex, hood_id = neighbourhoods(cliques, nbrs)
    K = cfg["n_labels"]
    model = energy_model(means, sizes, beta=cfg["beta"], sigma_min=cfg["sigma_min"],
                         n_labels=K, f=f)
    labels0, mu0, sigma0 = quantile_init(means, K, f)
    labels, mu, sigma, hood_e, em_iters, map_iters = golden_em(
        vertex, hood_id, len(cliques), n_regions, model, labels0, mu0, sigma0,
        max_em_iters=cfg["max_em_iters"], max_map_iters=cfg["max_map_iters"], f=f,
    )
    return RefSegmentation(
        segmentation=labels[sp].astype(np.int32), region_labels=labels,
        superpixels=sp.astype(np.int32), mu=mu.astype(np.float32),
        sigma=sigma.astype(np.float32),
        total_energy=float(hood_e.astype(np.float64).sum()),
        em_iters=em_iters, map_iters=map_iters, n_hoods=len(cliques),
        n_elements=len(vertex),
    )
