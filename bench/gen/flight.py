"""Flight-delay regression rows at the paper's §5 shape (Gal et al. 2014).

A copy of ``repro.data.synthetic.flight_like`` that materialises all rows
in one vectorised call, so the benchmark's data stays fixed when the
program's generator changes.  Row ``i`` is the same as the program's
generator gives for ``seed``: one Philox stream keyed by the seed, 16
uniform draws per row (8 covariates, 2 for Box-Muller noise, 6 spare).
"""
from __future__ import annotations

import numpy as np

# Population moments of the covariate columns (the uniform/discrete ranges
# below), fixed so that standardisation does not depend on the rows drawn.
FLIGHT_MEAN = np.array([6.5, 16.0, 4.0, 12.0, 12.0, 315.0, 2500.0, 25.0])
FLIGHT_STD = np.array([3.45, 8.94, 2.0, 6.93, 6.93, 164.5, 1385.6, 14.4])


def flight_rows(n: int, seed: int, start: int = 0, noise: float = 0.2):
    """Rows ``[start, start + n)``: ``{"mu": (n, 8) standardised
    covariates, "y": (n, 1) delays}``, float64."""
    bg = np.random.Philox(key=seed).advance(start * 4)   # 4 blocks per row
    u = np.random.Generator(bg).random((n, 16))
    eps = np.sqrt(-2.0 * np.log1p(-u[:, 8])) * np.cos(2 * np.pi * u[:, 9])
    x = np.empty((n, 8))
    x[:, 0] = 1 + np.floor(12 * u[:, 0])        # month
    x[:, 1] = 1 + np.floor(31 * u[:, 1])        # day of month
    x[:, 2] = 1 + np.floor(7 * u[:, 2])         # day of week
    x[:, 3] = 24.0 * u[:, 3]                    # departure hour
    x[:, 4] = 24.0 * u[:, 4]                    # arrival hour
    x[:, 5] = 30 + 570 * u[:, 5]                # airtime (min)
    x[:, 6] = 100 + 4800 * u[:, 6]              # distance (mi)
    x[:, 7] = 50 * u[:, 7]                      # plane age (yr)
    s = (x - FLIGHT_MEAN) / FLIGHT_STD
    f = (np.sin(1.3 * s[:, 3]) + 0.7 * np.cos(0.9 * s[:, 4])
         + 0.5 * s[:, 5] * np.exp(-0.5 * s[:, 6] ** 2)
         + 0.3 * np.tanh(s[:, 0] + 0.5 * s[:, 2]) - 0.2 * s[:, 7])
    y = f + noise * (1.0 + 0.3 * np.abs(s[:, 5])) * eps
    return {"mu": s, "y": y[:, None]}


def flight_start(rows: dict, m: int, seed: int):
    """Inducing inputs drawn from the first rows' covariates, unit
    hyper-parameters (the bring-up's start, with the draw keyed by the
    seed).  Returns ``(hyp, z)`` as float64 numpy."""
    first = rows["mu"][:max(4 * m, 4096)]
    rng = np.random.default_rng(seed)
    z = first[rng.choice(first.shape[0], m, replace=False)]
    hyp = {"log_sf2": np.float64(0.0), "log_ell": np.zeros(8),
           "log_beta": np.float64(1.0)}
    return hyp, z
