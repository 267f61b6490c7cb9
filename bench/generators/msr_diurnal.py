"""MSR-like diurnal demand: a copy of the program's ``msr_diurnal`` scenario.

The shape is ``repro.core.traces.msr_like_trace`` (business-hours and
evening humps, quiet weekends, multiplicative noise, rare flash spikes,
the generator's own rescale to PMR 4.63 and 40 jobs); the registry then
re-fits the realized peak-to-mean ratio and the mean
(:func:`bench.demand.fit_pmr`).  It is a synthetic stand-in for the MSR
Cambridge trace the paper measured, which the program does not ship.
"""
from __future__ import annotations

import numpy as np

from bench.demand import fit_pmr, scale_to_pmr

SLOTS_PER_DAY = 144          # 10-minute slots


def shape(rng: np.random.Generator, n_slots: int, *, noise: float = 0.08,
          spike_prob: float = 0.004) -> np.ndarray:
    """Unnormalized MSR-like shape, integer jobs as floats."""
    t = np.arange(n_slots)
    day_phase = 2 * np.pi * (t % SLOTS_PER_DAY) / SLOTS_PER_DAY
    diurnal = (
        0.25
        + np.clip(np.sin(day_phase - np.pi / 2), 0, None) ** 1.5
        + 0.35 * np.clip(np.sin(2 * day_phase - np.pi / 3), 0, None) ** 2
    )
    dow = (t // SLOTS_PER_DAY) % 7
    weekly = np.where(dow < 5, 1.0, 0.45)
    base = diurnal * weekly
    base = base * (1.0 + noise * rng.standard_normal(n_slots))
    spikes = (rng.uniform(size=n_slots) < spike_prob) * rng.uniform(2.0, 4.0, n_slots)
    base = np.clip(base + spikes, 0.02, None)
    # the generator's own fixed rescale (PMR 4.63, mean 40 jobs, rounded)
    a = scale_to_pmr(base, 4.63)
    a = a / a.mean() * 40.0
    return np.maximum(np.rint(a).astype(np.int64), 0).astype(np.float64)


def trace(seed: int, index: int, n_slots: int, *, target_pmr: float,
          mean_jobs: float, noise: float = 0.08, spike_prob: float = 0.004) -> np.ndarray:
    """(n_slots,) int64 demand: trace ``index`` of ``seed``, re-fit to
    ``target_pmr`` and ``mean_jobs``."""
    rng = np.random.default_rng((seed, index))
    return fit_pmr(shape(rng, n_slots, noise=noise, spike_prob=spike_prob),
                   float(target_pmr), float(mean_jobs))
