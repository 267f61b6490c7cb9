"""Demand for the benchmark's cells, made from the seed.

A configuration's ``demand`` block names its ``generator``: the file
``bench/generators/<generator>.py``, whose ``trace(seed, index, n_slots,
**params)`` draws trace ``index`` of a seed from
``numpy.random.default_rng((seed, index))``, the program's scenario
registry's convention.  This module holds what every generator shares: a
copy of the registry's realized-PMR re-fit (``repro.scenarios.registry``
``_fit_pmr``, with ``repro.core.traces.scale_to_pmr``), kept here so that a
change to the program cannot change the traffic it is measured on, and the
cap at the fleet's size.
"""
from __future__ import annotations

import numpy as np

from bench import discover

PMR_TOL = 0.05               # realized-PMR tolerance of the re-fit
PMR_REFITS = 4               # secant corrections before giving up


def scale_to_pmr(a: np.ndarray, target_pmr: float, tol: float = 1e-3) -> np.ndarray:
    """a' = K * a^gamma with the mean kept, gamma bisected to the target
    peak-to-mean ratio (the paper's Sec. V-D transform)."""
    a = np.clip(np.asarray(a, dtype=np.float64), 1e-9, None)
    lo, hi = 0.05, 20.0
    for _ in range(200):
        gamma = 0.5 * (lo + hi)
        b = a ** gamma
        b = b / b.mean()
        pmr = b.max()
        if abs(pmr - target_pmr) < tol:
            break
        if pmr < target_pmr:
            lo = gamma
        else:
            hi = gamma
    b = a ** gamma
    return b / b.mean() * a.mean()


def _quantize(a: np.ndarray, mean_jobs: float) -> np.ndarray:
    mean = a.mean()
    if mean > 0:
        a = a / mean * mean_jobs
    return np.maximum(np.rint(a), 0).astype(np.int64)


def fit_pmr(a: np.ndarray, target: float, mean_jobs: float) -> np.ndarray:
    """Integer trace whose realized PMR lies within ``PMR_TOL`` of target
    (the closest of ``PMR_REFITS + 1`` secant-corrected attempts)."""
    goal = target
    best, best_err = None, np.inf
    for _ in range(PMR_REFITS + 1):
        q = _quantize(scale_to_pmr(a, goal), mean_jobs)
        mean = q.mean()
        realized = float(q.max() / mean) if mean > 0 else 0.0
        err = abs(realized - target) / target
        if err < best_err:
            best, best_err = q, err
        if err <= PMR_TOL or realized <= 0:
            break
        goal = max(1.0 + 1e-6, goal * target / realized)
    return best


def generate(demand: dict, seed: int, index: int, n_slots: int, clip_to: int,
             root=discover.ROOT) -> np.ndarray:
    """Trace ``index`` of ``seed`` from a configuration's ``demand`` block,
    capped at the fleet size ``clip_to``."""
    params = dict(demand)
    gen = discover.module("generators", params.pop("generator"), root)
    return np.minimum(gen.trace(seed, index, n_slots, **params), clip_to)
