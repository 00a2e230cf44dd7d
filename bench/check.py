"""The comparison that decides ``correct``.

Every answer the window produced for a checked slice is compared with the
plain reference (``bench/reference.py``) run on that slice's image.  The
checked slices are those whose plan the window kept (the driver decides:
all of them, or a sample drawn from the seed).  Each number is the worst
over the answers it applies to:

* ``region_label_mismatch``  share of regions whose label differs;
* ``pixel_mismatch``         share of pixels whose segment differs, over the
                             answers whose segmentation was kept (a sample);
* ``mu_rel_err``             largest relative error of a class mean;
* ``energy_rel_err``         relative error of the final total energy;
* ``superpixel_mismatch``    share of pixels whose oversegmentation region
                             differs (the plan layer's first product);
* ``hood_count_gap``         relative gap in the number of neighbourhoods or
                             of neighbourhood elements (its last products).

A number with nothing to compare reads ``inf``, which no limit admits.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference

NUMBERS = ("region_label_mismatch", "pixel_mismatch", "mu_rel_err",
           "energy_rel_err", "superpixel_mismatch", "hood_count_gap")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def compare(answers, slices, cfg: dict) -> dict:
    """Worst reading of each number over the window's answers."""
    checked = {s.index for s in slices if s.plan is not None} & {a.slice for a in answers}
    refs = {s.index: reference.segment(s.image, cfg) for s in slices
            if s.index in checked}
    worst = {k: [] for k in NUMBERS}
    for a in answers:
        ref = refs.get(a.slice)
        if ref is None:
            continue
        worst["region_label_mismatch"].append(float(np.mean(a.region_labels != ref.region_labels)))
        worst["mu_rel_err"].append(_rel(a.mu, ref.mu))
        worst["energy_rel_err"].append(_rel(a.total_energy, ref.total_energy))
        if a.segmentation is not None:
            worst["pixel_mismatch"].append(float(np.mean(a.segmentation != ref.segmentation)))
    for s in slices:
        ref = refs.get(s.index)
        if ref is None or s.plan is None:
            continue
        sp = np.asarray(s.plan.problem.labels_px)
        worst["superpixel_mismatch"].append(float(np.mean(sp != ref.superpixels)))
        h = s.plan.problem.hoods
        worst["hood_count_gap"].append(max(abs(h.n_hoods - ref.n_hoods) / ref.n_hoods,
                                           abs(h.n_elements - ref.n_elements) / ref.n_elements))
    return {k: max(v) if v else math.inf for k, v in worst.items()}


def within(numbers: dict, limits: dict) -> bool:
    """Whether every number is at or under its limit."""
    return all(numbers[k] <= limits[k] for k in limits)
