"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel tests sweep against
(assert_allclose over shapes/dtypes), and the dispatch fallback used by
``ops.py`` when Pallas is not wanted (e.g. eager CPU paths).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def segment_reduce(
    values: Array, segment_ids: Array, num_segments: int, op: str = "add"
) -> Array:
    """ReduceByKey oracle: jax.ops.segment_* over a 1D value array."""
    if op == "add":
        return jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)
    if op == "min":
        return jax.ops.segment_min(values, segment_ids, num_segments=num_segments)
    raise ValueError(op)


def mrf_min_energy(
    y: Array,
    w: Array,
    n1_e: Array,
    nall_e: Array,
    xf: Array,
    mu: Array,
    sigma: Array,
    beta: Array | float,
) -> Tuple[Array, Array]:
    """Fused MRF energy + per-element 2-label min (oracle).

    Mirrors ``repro.core.pmrf.energy.label_energies`` +
    ``min_energies_static`` for the binary-label case, on pre-gathered
    per-element arrays.
    """
    denom = jnp.maximum(nall_e - 1.0, 1.0)

    def energy(l):
        d = y - mu[l]
        data = w * (d * d / (2.0 * sigma[l] * sigma[l]) + jnp.log(sigma[l]))
        if l == 1:
            diff = (nall_e - n1_e) - (1.0 - xf)
        else:
            diff = n1_e - xf
        return data + beta * jnp.maximum(diff, 0.0) / denom

    e0, e1 = energy(0), energy(1)
    min_e = jnp.minimum(e0, e1)
    arg = (e1 < e0).astype(jnp.int32)
    return min_e, arg


def fused_map_step(
    y: Array,
    w: Array,
    cnt_e: Array,
    nall_e: Array,
    xf: Array,
    valid: Array,
    hood_id: Array,
    vertex: Array,
    mu: Array,
    sigma: Array,
    beta: Array | float,
    *,
    n_hoods: int,
    n_vertices: int,
) -> Tuple[Array, Array, Array, Array]:
    """Oracle for the fused K-ary MAP-iteration kernel (``map_step.py``).

    Same energy expressions as ``energy.label_energies`` (identical op
    order, so argmins agree bitwise), followed by the keyed reductions the
    kernel performs as one-hot contractions: the per-hood energy sum and
    the per-(label, vertex) vote counts.  ``cnt_e`` is the (K, H) gathered
    per-element neighborhood label-count matrix and ``mu``/``sigma`` are
    (K,); ``valid`` masks padding lanes.  Returns
    (min_e, arg, hood_e, votes) with ``votes`` shaped (K, n_vertices).
    """
    n_labels = int(mu.shape[0])
    denom = jnp.maximum(nall_e - 1.0, 1.0)
    es = []
    for l in range(n_labels):
        d = y - mu[l]
        e = w * (d * d / (2.0 * sigma[l] * sigma[l]) + jnp.log(sigma[l]))
        eq = (xf == l).astype(jnp.float32)
        e = e + beta * jnp.maximum(
            (nall_e - cnt_e[l]) - (1.0 - eq), 0.0
        ) / denom * valid
        es.append(e)
    energies = jnp.stack(es)

    min_e = jnp.min(energies, axis=0)
    arg = jnp.argmin(energies, axis=0).astype(jnp.int32)
    seg_h = jnp.where(valid > 0, hood_id, n_hoods).astype(jnp.int32)
    seg_v = jnp.where(valid > 0, vertex, n_vertices).astype(jnp.int32)
    hood_e = jax.ops.segment_sum(
        min_e * valid, seg_h, num_segments=n_hoods + 1
    )[:n_hoods]
    votes = jnp.stack(
        [
            jax.ops.segment_sum(
                (arg == l).astype(jnp.float32) * valid,
                seg_v,
                num_segments=n_vertices + 1,
            )[:n_vertices]
            for l in range(n_labels)
        ]
    )
    return min_e, arg, hood_e, votes


def fused_em_tick(
    y: Array,
    w: Array,
    nall_e: Array,
    xf: Array,
    valid: Array,
    hood_id: Array,
    vertex: Array,
    region_mean: Array,
    region_weight: Array,
    hist: Array,
    mu: Array,
    sigma: Array,
    beta: Array | float,
    *,
    n_hoods: int,
    n_vertices: int,
    precision: str = "f32",
    conv_tol: float = 1.0e-4,
) -> Tuple[Array, ...]:
    """Oracle for the fused EM-tick kernel (``em_tick.py``).

    The energy expressions come from the SAME helper the kernel uses
    (``em_tick.label_energies_blocked``), so energies/argmins agree
    bitwise at both precisions.  The keyed reductions run in
    ``jax.ops.segment_sum`` element order: counts and votes are
    integer-exact (bitwise equal to the kernel's one-hot dots), the
    per-hood energy sums match ``fused_map_step``'s reference order, and
    the M-step sums match ``energy.update_parameters_stats``'s order —
    which is why this composition stays bitwise against the golden
    fixtures while the compiled kernel's dot-ordered M-sums may drift in
    final ulps.  Returns ``(labels, hood_e, votes, conv, sum_w, sum_wy,
    sum_wyy)``.
    """
    from repro.kernels import em_tick as _em_tick

    n_labels = int(mu.shape[0])
    seg_h = jnp.where(valid > 0, hood_id, n_hoods).astype(jnp.int32)
    xi = jnp.clip(xf.astype(jnp.int32), 0, n_labels - 1)
    counts = jax.ops.segment_sum(
        valid, seg_h * n_labels + xi, num_segments=(n_hoods + 1) * n_labels
    ).reshape(n_hoods + 1, n_labels)
    cnt_e = counts[jnp.clip(hood_id, 0, n_hoods - 1)].T  # (K, H)

    energies = _em_tick.label_energies_blocked(
        y, w, cnt_e, nall_e, xf, valid, mu[:, None], sigma[:, None], beta,
        precision=precision,
    )
    if precision == "bf16":
        energies = _em_tick.round_to_bf16(energies)
    min_e = jnp.min(energies, axis=0)
    arg = jnp.argmin(energies, axis=0).astype(jnp.int32)

    hood_e = jax.ops.segment_sum(
        min_e * valid, seg_h, num_segments=n_hoods + 1
    )[:n_hoods]
    seg_v = jnp.where(valid > 0, vertex, n_vertices).astype(jnp.int32)
    votes = (
        jax.ops.segment_sum(
            valid,
            seg_v * n_labels + arg,
            num_segments=(n_vertices + 1) * n_labels,
        )
        .reshape(n_vertices + 1, n_labels)
        .T[:, :n_vertices]
    )
    labels = jnp.argmax(votes, axis=0).astype(jnp.int32)
    labels = labels.at[n_vertices - 1].set(0)

    sum_w = jax.ops.segment_sum(region_weight, labels, num_segments=n_labels)
    sum_wy = jax.ops.segment_sum(
        region_weight * region_mean, labels, num_segments=n_labels
    )
    sum_wyy = jax.ops.segment_sum(
        region_weight * region_mean * region_mean, labels, num_segments=n_labels
    )

    scale = jnp.maximum(jnp.abs(hood_e), 1.0)
    ok = jnp.abs(hood_e - hist[0, :n_hoods]) < conv_tol * scale
    for r in range(int(hist.shape[0]) - 2):
        ok = ok & (jnp.abs(hist[r, :n_hoods] - hist[r + 1, :n_hoods]) < conv_tol * scale)
    conv = jnp.all(ok)
    return labels, hood_e, votes, conv, sum_w, sum_wy, sum_wyy


def flash_attention(
    q: Array, k: Array, v: Array, *, causal: bool = False, scale: float | None = None
) -> Array:
    """Attention oracle: naive softmax(QK^T)V with GQA head mapping.

    q: (B, Hq, S, D), k/v: (B, Hkv, S, D) with Hq % Hkv == 0.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)
