"""Pallas TPU kernel: the ENTIRE EM tick in one launch (DESIGN.md §16).

``map_step.py`` fuses the MAP iteration body, but a full EM micro-step
still surrounds that launch with separate XLA ops: the per-(hood, label)
count reduction feeding the smoothness term, the M-step accumulators
(per-label weight/sum/sumsq for mu/sigma), and the convergence-window
reduction.  On the ticked serving driver that is several kernel
boundaries per lane-tick.  This kernel collapses the whole tick body —

    pass 0:  per-(hood, label) counts of the current label field
    pass 1:  K-ary energies -> per-element min/argmin -> per-hood energy
             sums -> label votes
    final:   plurality-vote labels, M-step accumulators over regions,
             convergence flag from the energy-history window

— into ONE ``pallas_call``.  Two deliberate layout changes versus
``map_step.py``:

* **label-blocked K layout** — the old kernel used ``grid=(n_blocks, K)``,
  revisiting every element block K times (grid replication: K=5 costs
  ~2.5x K=2 in grid steps alone).  Here the grid is ``(2, n_blocks)``
  (count pass, then map pass) and all K labels are computed per block as
  a ``(K, BLOCK)`` tile: K lives on the sublane axis of the vector unit,
  so label count scales by block occupancy, not launch count.
* **two passes over the element stream** — the smoothness term needs the
  completed per-(hood, label) counts before any energy can be evaluated,
  so pass 0 streams the element blocks once accumulating counts into a
  revisited ``(K, s_pad)`` output (integer-exact one-hot dots), and pass
  1 streams them again gathering each block's counts back with the
  transposed one-hot — double-buffered element blocks, zero XLA ops
  between the count and the energies.

Mixed precision (``precision="bf16"``): the energy expressions (the
O(K*H) arithmetic) run in bfloat16 while every accumulator — counts,
hood energy sums, votes, M-step sums — stays float32.  Counts, argmins,
and votes are integer-valued, so the label trajectory is typically
unchanged; mu/sigma pick up bounded drift (the golden harness's bf16
tolerance tier, tests/test_golden.py).

Bitwise contract at f32: the energy expressions, min/argmin fold, and
the per-hood/vote one-hot contractions replicate ``map_step.py``'s op
order exactly, so ``min_e``/``arg``/``hood_e``/``votes`` are bit-identical
to the label-replicated kernel.  The compiled kernel's M-step sums are
one-hot MXU dots whose accumulation order differs from
``jax.ops.segment_sum``'s element order, so on the chip mu/sigma may
differ in final ulps from the unfused composition.  In interpret mode the
kernel takes them with ``segment_sum`` instead, the order of the
reference ``ref.fused_em_tick`` and of the golden oracle, so the
interpreted kernel stays bitwise against the golden fixtures; the
convergence predicate is the same arithmetic as ``em._window_converged``
on identical hood sums.

Inputs (all (H,) f32 unless noted):
  y, w, nall_e, xf, valid     as in ``map_step.py``
  hood_id / vertex            (H,) int32 segment ids
  region_mean, region_weight  (n_vertices,) the M-step's region stats
  hist                        (WINDOW+1, n_hoods) per-hood energy history
  mu, sigma                   (K,); beta scalar

Outputs: labels (n_vertices,) i32, hood_e (n_hoods,) f32,
votes (K, n_vertices) f32, conv () bool, and the M-step accumulators
sum_w/sum_wy/sum_wyy (each (K,) f32).

Padding convention matches ``map_step.py``: float lanes pad with zeros,
ids pad with 2**30 (never matching a one-hot row), regions/hoods pad to
SEG_ALIGN with zero weight — all padding is inert in every reduction.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import tpu_compiler_params

BLOCK = 1024     # hood elements per value tile
SEG_ALIGN = 128  # segment-axis padding (MXU lane width)


def label_energies_blocked(
    y, w, cnt, nall, xf, valid, mu, sig, beta, *, precision: str = "f32"
):
    """(K, N) label energies from label-blocked inputs.

    Shared by the kernel (per (K, BLOCK) tile) and the XLA reference
    (whole (K, H) array) so both paths run the *identical* elementwise
    op sequence — and, at f32, the identical sequence as
    ``energy.label_energies`` / ``map_step.py``, keeping argmins bitwise.
    ``mu``/``sig`` arrive as (K, 1) columns: the TPU lowering refuses a
    bf16 (K,) -> (K, 1) shape cast inside the kernel.
    ``precision="bf16"`` casts every energy operand to bfloat16; the
    result comes back widened to f32, so the min/argmin fold and every
    accumulator run in f32 on both paths (v5e has no bf16 compare).  The
    compiled kernel's bf16 energies widen exactly; on XLA:CPU callers
    apply :func:`round_to_bf16` first (see there).
    """
    cd = jnp.bfloat16 if precision == "bf16" else jnp.float32
    # The label-match mask is compared in f32 whatever the precision (the
    # labels are small integers, exact in both): Mosaic has no float iota
    # and v5e has no bf16 compare.
    labf = jax.lax.broadcasted_iota(jnp.int32, cnt.shape, 0).astype(jnp.float32)
    eq = (xf.astype(jnp.float32)[None, :] == labf).astype(cd)
    y = y.astype(cd)
    w = w.astype(cd)
    nall = nall.astype(cd)
    valid = valid.astype(cd)
    cnt = cnt.astype(cd)
    mu = mu.astype(cd)
    sig = sig.astype(cd)
    beta = jnp.asarray(beta).astype(cd)
    denom = jnp.maximum(nall - 1.0, 1.0)
    d = y[None, :] - mu
    e = w[None, :] * (d * d / (2.0 * sig * sig) + jnp.log(sig)) + beta * jnp.maximum(
        (nall[None, :] - cnt) - (1.0 - eq), 0.0
    ) / denom[None, :] * valid[None, :]
    return e.astype(jnp.float32)


def round_to_bf16(x):
    """Round f32 ``x`` to the nearest bfloat16 (ties to even), kept in f32.

    A no-op on values already in bfloat16, as the compiled kernel's are.
    XLA:CPU may carry bf16 intermediates at f32 ("excess precision")
    differently in the kernel's interpreted blocks and in the reference's
    whole-array graph; both round here, with integer ops that no float
    simplification can remove, to pin the same bf16 energies.
    """
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = b + 0x7FFF + (jax.lax.shift_right_logical(b, 16) & 1)
    return jax.lax.bitcast_convert_type(b & -0x10000, jnp.float32)


def fold_min_argmin(e):
    """Per-column (min, argmin) of a (K, N) energy tile as an unrolled fold
    over the K rows; strict '<' keeps the lowest label on ties (bitwise
    ``jnp.argmin`` semantics).  Shared by both fused kernels."""
    min_e = e[0]
    arg = jnp.zeros(e.shape[1:], jnp.int32)
    for l in range(1, e.shape[0]):
        take = e[l] < min_e
        min_e = jnp.where(take, e[l], min_e)
        arg = jnp.where(take, l, arg).astype(jnp.int32)
    return min_e, arg


def _kernel(
    beta_ref,
    mu_ref,
    sig_ref,
    y_ref,
    w_ref,
    nall_ref,
    xf_ref,
    valid_ref,
    hood_ref,
    vert_ref,
    rm_ref,
    rw_ref,
    hist_ref,
    labels_ref,
    hood_e_ref,
    votes_ref,
    counts_ref,
    stats_ref,
    *,
    n_labels: int,
    n_blocks: int,
    sentinel: int,
    conv_tol: float,
    precision: str,
    interpret: bool,
):
    p = pl.program_id(0)   # pass: 0 = counts, 1 = map + finalize
    i = pl.program_id(1)   # element block (innermost, sequential)

    xf = xf_ref[...]
    valid = valid_ref[...]
    s_rows = hood_e_ref.shape[0]
    rows_h = jax.lax.broadcasted_iota(jnp.int32, (s_rows, BLOCK), 0)
    onehot_h = (rows_h == hood_ref[...][None, :]).astype(jnp.float32)

    @pl.when((p == 0) & (i == 0))
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        hood_e_ref[...] = jnp.zeros_like(hood_e_ref)
        votes_ref[...] = jnp.zeros_like(votes_ref)
        labels_ref[...] = jnp.zeros_like(labels_ref)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    # Pass 0: per-(hood, label) counts of the current label field — the
    # one quantity the energies need that depends on the evolving labels.
    # Integer-exact one-hot contractions, so the values are bitwise equal
    # to the unfused compound-key segment sum.
    @pl.when(p == 0)
    def _count_pass():
        for l in range(n_labels):
            sel = (xf == jnp.float32(l)).astype(jnp.float32) * valid
            counts_ref[l, :] += jnp.dot(
                onehot_h, sel, preferred_element_type=jnp.float32
            )

    # Pass 1: gather the block's counts back through the transposed
    # one-hot (exact: integer dot), evaluate all K energies as one
    # label-blocked (K, BLOCK) tile, fold min/argmin across the sublane
    # axis, and accumulate the keyed reductions.
    @pl.when(p == 1)
    def _map_pass():
        cnt_blk = jnp.dot(
            counts_ref[...], onehot_h, preferred_element_type=jnp.float32
        )
        e = label_energies_blocked(
            y_ref[...], w_ref[...], cnt_blk, nall_ref[...], xf, valid,
            mu_ref[...], sig_ref[...], beta_ref[0], precision=precision,
        )
        if interpret and precision == "bf16":
            e = round_to_bf16(e)
        min_e, arg = fold_min_argmin(e)

        hood_e_ref[...] += jnp.dot(
            onehot_h, min_e * valid, preferred_element_type=jnp.float32
        )
        v_rows = votes_ref.shape[1]
        rows_v = jax.lax.broadcasted_iota(jnp.int32, (v_rows, BLOCK), 0)
        onehot_v = (rows_v == vert_ref[...][None, :]).astype(jnp.float32)
        for l2 in range(n_labels):
            sel = (arg == l2).astype(jnp.float32) * valid
            votes_ref[l2, :] += jnp.dot(
                onehot_v, sel, preferred_element_type=jnp.float32
            )

    # Final grid step: votes and hood sums are complete — finish the tick
    # (labels, M-step accumulators, convergence) without leaving VMEM.
    @pl.when((p == 1) & (i == n_blocks - 1))
    def _finalize():
        votes = votes_ref[...]
        r_pad = votes.shape[1]
        best = votes[0]
        lab = jnp.zeros((r_pad,), jnp.int32)
        for l in range(1, n_labels):
            take = votes[l] > best      # strict: ties keep the lowest label
            best = jnp.where(take, votes[l], best)
            lab = jnp.where(take, l, lab).astype(jnp.int32)
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, r_pad), 1)[0]
        lab = jnp.where(ridx == sentinel, 0, lab)
        labels_ref[...] = lab

        # M-step accumulators: one-hot contraction of the region stats by
        # the NEW labels (padded regions carry zero weight — inert).
        wr = rw_ref[...]
        yr = rm_ref[...]
        if interpret:
            # XLA:CPU's dot does not add in element order; a scatter-add
            # does, which keeps the interpreted kernel bitwise with the
            # reference and the golden oracle.
            def msum(v):
                return jax.ops.segment_sum(v, lab, num_segments=n_labels)
        else:
            rows_k = jax.lax.broadcasted_iota(jnp.int32, (n_labels, r_pad), 0)
            onehot_l = (rows_k == lab[None, :]).astype(jnp.float32)

            def msum(v):
                return jnp.dot(onehot_l, v, preferred_element_type=jnp.float32)
        sum_w = msum(wr)
        sum_wy = msum(wr * yr)
        sum_wyy = msum(wr * yr * yr)

        # Convergence window — the same arithmetic as em._window_converged
        # on [hood_e, hist[0], ..., hist[W-1]]; padded hoods compare
        # 0-vs-0 and are trivially converged.  The iteration-count gate
        # (i > WINDOW) is applied by the caller.
        he = hood_e_ref[...]
        h = hist_ref[...]
        window = h.shape[0] - 1
        tol = jnp.float32(conv_tol)
        scale = jnp.maximum(jnp.abs(he), 1.0)
        ok = jnp.abs(he - h[0]) < tol * scale
        for r in range(window - 1):
            ok = ok & (jnp.abs(h[r] - h[r + 1]) < tol * scale)
        conv = jnp.all(ok)

        stats_ref[...] = jnp.stack(
            [sum_w, sum_wy, sum_wyy,
             jnp.broadcast_to(conv.astype(jnp.float32), (n_labels,))]
        )


@functools.partial(
    jax.jit,
    static_argnames=("n_hoods", "n_vertices", "precision", "conv_tol", "interpret"),
)
def fused_em_tick_pallas(
    y: jax.Array,
    w: jax.Array,
    nall_e: jax.Array,
    xf: jax.Array,
    valid: jax.Array,
    hood_id: jax.Array,
    vertex: jax.Array,
    region_mean: jax.Array,
    region_weight: jax.Array,
    hist: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    beta,
    *,
    n_hoods: int,
    n_vertices: int,
    precision: str = "f32",
    conv_tol: float = 1.0e-4,
    interpret: bool,
) -> Tuple[jax.Array, ...]:
    """One fused launch for the whole EM tick body.

    Returns ``(labels, hood_e, votes, conv, sum_w, sum_wy, sum_wyy)``.
    """
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}; have ('f32', 'bf16')")
    n_labels = int(mu.shape[0])
    n = y.shape[0]
    n_pad = -(-n // BLOCK) * BLOCK
    s_pad = -(-n_hoods // SEG_ALIGN) * SEG_ALIGN
    r_pad = -(-n_vertices // SEG_ALIGN) * SEG_ALIGN
    n_blocks = n_pad // BLOCK
    w1 = int(hist.shape[0])  # WINDOW + 1 history rows

    def padf(x):
        return jnp.zeros((n_pad,), jnp.float32).at[:n].set(x.astype(jnp.float32))

    def padi(x):
        return jnp.full((n_pad,), 2 ** 30, jnp.int32).at[:n].set(
            x.astype(jnp.int32)
        )

    rm = jnp.zeros((r_pad,), jnp.float32).at[:n_vertices].set(
        region_mean.astype(jnp.float32)
    )
    rw = jnp.zeros((r_pad,), jnp.float32).at[:n_vertices].set(
        region_weight.astype(jnp.float32)
    )
    hist_p = jnp.zeros((w1, s_pad), jnp.float32).at[:, :n_hoods].set(
        hist.astype(jnp.float32)
    )

    blockspec_e = pl.BlockSpec((BLOCK,), lambda p, i: (i,))

    def full(shape):
        return pl.BlockSpec(shape, lambda p, i, _z=(0,) * len(shape): _z)

    labels, hood_e, votes, _counts, stats = pl.pallas_call(
        functools.partial(
            _kernel,
            n_labels=n_labels,
            n_blocks=n_blocks,
            sentinel=n_vertices - 1,
            conv_tol=float(conv_tol),
            precision=precision,
            interpret=interpret,
        ),
        grid=(2, n_blocks),
        in_specs=[
            full((1,)),            # beta
            full((n_labels, 1)),   # mu (column)
            full((n_labels, 1)),   # sigma (column)
            blockspec_e,           # y
            blockspec_e,           # w
            blockspec_e,           # nall_e
            blockspec_e,           # xf
            blockspec_e,           # valid
            blockspec_e,           # hood_id
            blockspec_e,           # vertex
            full((r_pad,)),        # region_mean
            full((r_pad,)),        # region_weight
            full((w1, s_pad)),     # hist
        ],
        out_specs=[
            full((r_pad,)),            # labels (written at the final step)
            full((s_pad,)),            # hood_e (accumulated, pass 1)
            full((n_labels, r_pad)),   # votes (accumulated, pass 1)
            full((n_labels, s_pad)),   # counts (accumulated, pass 0)
            full((4, n_labels)),       # stats: sum_w/sum_wy/sum_wyy/conv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad,), jnp.int32),
            jax.ShapeDtypeStruct((s_pad,), jnp.float32),
            jax.ShapeDtypeStruct((n_labels, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_labels, s_pad), jnp.float32),
            jax.ShapeDtypeStruct((4, n_labels), jnp.float32),
        ],
        # Every output block is revisited across the grid (counts/hood_e/
        # votes accumulate, labels/stats are written at the final step) —
        # declare the whole grid sequential ("arbitrary") explicitly; the
        # analysis race checker (PL104, DESIGN.md §15) requires the
        # revisit-safety assumption to be stated, not inherited.
        compiler_params=tpu_compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="fused_em_tick",
    )(
        jnp.asarray(beta, jnp.float32).reshape(1),
        mu.astype(jnp.float32).reshape(n_labels, 1),
        sigma.astype(jnp.float32).reshape(n_labels, 1),
        padf(y),
        padf(w),
        padf(nall_e),
        padf(xf),
        padf(valid),
        padi(hood_id),
        padi(vertex),
        rm,
        rw,
        hist_p,
    )

    conv = stats[3, 0] > 0.0
    return (
        labels[:n_vertices],
        hood_e[:n_hoods],
        votes[:, :n_vertices],
        conv,
        stats[0],
        stats[1],
        stats[2],
    )
