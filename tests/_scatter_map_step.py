"""The ``static`` MAP iteration's reductions as keyed XLA scatters, kept as
a test oracle for :func:`repro.core.pmrf.energy.map_step_runs`.

This is what the single-device static and faithful routes ran before they
reduced over the plan's sorted runs: per-(hood, label) counts and hood
sizes by a segment sum over the key ``hood_id * K + x``, gathered back to
the elements; hood energy sums by a segment sum over ``hood_id``; votes by
a scatter-add into ``(V + 1) * K``.  :func:`run_em_scatter` runs the EM
driver with it in place of the run reductions.
"""

from __future__ import annotations

from unittest import mock

import jax

from repro.core.pmrf import collectives
from repro.core.pmrf import em as em_mod
from repro.core.pmrf import energy as E


def scatter_map_step(
    hoods, model, sctx, labels, mu, sigma, *, faithful=False, backend=None
):
    """(new labels, hood energy sums), with the signature of
    ``E.map_step_runs`` (``sctx`` is unused: nothing is hoisted)."""
    n_labels = int(mu.shape[0])
    counts = E.hood_label_counts(hoods, labels, n_labels, backend=backend)
    energies = E.label_energies(
        hoods, model, labels, mu, sigma, hood_counts=counts, backend=backend
    )
    if faithful:
        min_e, arg = E.min_energies_faithful(hoods, energies, backend=backend)
    else:
        min_e, arg = E.min_energies_static(energies)
    hood_e = E.hood_energy_sums(hoods, min_e, backend=backend)
    return E.vote_labels(hoods, arg, hoods.n_regions, n_labels), hood_e


def run_em_scatter(hoods, model, labels0, mu0, sigma0, config):
    """``run_em`` with :func:`scatter_map_step` as its MAP iteration (a
    fresh trace each call, so no cached program of the run path is hit)."""
    with mock.patch.object(E, "map_step_runs", scatter_map_step):
        return jax.jit(
            lambda *a: em_mod._em_driver(*a, config, collectives.LOCAL)
        )(hoods, model, labels0, mu0, sigma0)
