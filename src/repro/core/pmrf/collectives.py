"""Collective context: sharded execution as a *parametrization* of EM.

The EM/MAP driver (``em.py``) touches cross-element state in exactly four
places; everything else in an iteration is elementwise over hood elements
or operates on tiny replicated arrays (labels, mu/sigma).  The four touch
points, and what they become when hood elements are block-partitioned over
a mesh axis (the hybrid distributed PMRF of the paper's §5 / [15]):

  1. per-(hood, label) counts (smoothness ctx)    Scatter/ReduceByKey -> +psum
  2. per-hood energy sums (convergence input)     ReduceByKey(Add)    -> +psum
  3. label votes (scatter into the global field)  Scatter(Add)        -> +psum
  4. convergence decision                          AND                 -> pmin

The label count K needs no hook of its own (DESIGN.md §13): callers fold
K into the *key spaces* of touch points 1 and 3 (``dpp.compound_key`` —
``hood_id * K + x`` and ``vertex * K + argmin``), so the same psum'd
segment sums carry the extra axis; counts and votes stay integer-valued,
keeping the cross-shard sums exact and K-ary sharded labels bitwise equal
to single-device.

:class:`ReduceCtx` carries those four hooks.  The single-device context
(``axis=None``, the module constant :data:`LOCAL`) lowers each to the plain
DPP primitive (the single-device static and faithful MAP iteration needs
only hook 4: it reduces over the plan's sorted runs, DESIGN.md §13); the
sharded context (``axis="<mesh axis>"``) wraps the local
primitive in the matching ``dpp_sharded`` collective.  The driver is
written once against the context, so ``distributed.py`` no longer forks
the MAP/EM loop bodies — it just builds a sharded context and ``shard_map``s
the same driver (DESIGN.md §11).

The context is a frozen, hashable dataclass: it rides through ``jax.jit``
static arguments, and two traces with different contexts never alias.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dpp, dpp_sharded

Array = jax.Array


@dataclass(frozen=True)
class ReduceCtx:
    """The EM driver's cross-shard reduction hooks (see module docstring).

    ``axis`` is ``None`` for single-device execution or the mesh axis name
    when running inside a ``shard_map`` region over that axis.
    """

    axis: Optional[str] = None

    @property
    def sharded(self) -> bool:
        return self.axis is not None

    def psum(self, x: Array) -> Array:
        """Sum a replicated-shape partial result across shards (identity
        when single-device).  Used where a kernel already produced the
        local keyed reduction (the fused static-pallas path: collectives
        stay outside the kernel)."""
        if self.axis is None:
            return x
        return jax.lax.psum(x, self.axis)

    def segment_sum(
        self,
        segment_ids: Array,
        values: Array,
        num_segments: int,
        *,
        backend: Optional[str] = None,
        where: Optional[Array] = None,
    ) -> Array:
        """Touch points 1 & 2: ReduceByKey(Add) over a *global* segment id
        space.  Local backend-dispatched reduction, psum'd when sharded.

        ``where`` masks contributions before the reduction (masked lanes
        contribute exact zeros) — the ticked serving driver passes its
        per-lane active flag here so a retired-but-not-yet-replaced lane's
        stale state never reaches a reduction.  ``where=True`` is a bitwise
        no-op for live lanes (a select, never an arithmetic rewrite).
        """
        if where is not None:
            values = jnp.where(where, values, jnp.zeros((), values.dtype))
        if self.axis is None:
            return dpp.reduce_by_key(
                segment_ids, values, num_segments, op="add", backend=backend
            )
        return dpp_sharded.global_reduce_by_key(
            segment_ids, values, num_segments, self.axis, op="add", backend=backend
        )

    def vote_scatter(
        self,
        values: Array,
        indices: Array,
        out_size: int,
        *,
        where: Optional[Array] = None,
    ) -> Array:
        """Touch point 3: Scatter(Add) into the global vertex vote field.
        ``where`` masks votes exactly like :meth:`segment_sum`'s mask."""
        if where is not None:
            values = jnp.where(where, values, jnp.zeros((), values.dtype))
        local = dpp.scatter_(values, indices, out_size, mode="add")
        return self.psum(local)

    def all_converged(
        self, flags: Array, *, active: Optional[Array] = None
    ) -> Array:
        """Touch point 4: the global convergence AND.  Flags are computed
        from psum'd (replicated) energy sums so shards agree by
        construction; the pmin makes the decision robust to any future
        shard-local convergence input.

        ``active`` makes the decision *per lane* instead of global: an
        inactive (retired / empty-slot) lane reports converged immediately,
        so a pool-wide reduction over lanes is never held hostage by lanes
        that are no longer running — the masking contract of the ticked
        serving driver (DESIGN.md §12).
        """
        if self.axis is None:
            conv = jnp.all(flags)
        else:
            conv = dpp_sharded.global_all_converged(flags, self.axis)
        if active is None:
            return conv
        return jnp.where(active, conv, jnp.bool_(True))


#: The single-device context — the default for ``run_em``.
LOCAL = ReduceCtx(axis=None)


__all__ = ["ReduceCtx", "LOCAL"]
