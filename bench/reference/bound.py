"""The collapsed variational bound from summed statistics, in its textbook
form (Titsias 2009; Gal et al. 2014 eq. 3.3), written from the papers and
not from the program:

  F = -nd/2 log 2pi + nd/2 log b + d/2 log|Kmm| - d/2 log|Kmm + b D|
      - b/2 A - bd/2 B + bd/2 Tr(Kmm^-1 D) + b^2/2 Tr(C^T (Kmm + b D)^-1 C)
      - KL

with Kmm = k(Z, Z) + (jitter sf2 + 1e-12) I, the jitter the configuration
states.  Both determinants and both inverses come from Cholesky factors of
the two matrices as written, not from a whitened form.
"""
from __future__ import annotations

import jax.numpy as jnp
import jax.scipy.linalg as jsl


def sq_dist(a, b, inv_l2):
    """sum_q (a_q - b_q)^2 / l2_q for every pair of rows, as
    |a|^2 + |b|^2 - 2 a.b in scaled coordinates: the products run on the
    matrix unit, and float64 resolves the difference to ~1e-14."""
    sa, sb = a * jnp.sqrt(inv_l2), b * jnp.sqrt(inv_l2)
    return (jnp.sum(sa * sa, -1)[:, None] + jnp.sum(sb * sb, -1)[None, :]
            - 2.0 * sa @ sb.T)


def se_ard(hyp, a, b):
    """SE-ARD covariance sf2 exp(-1/2 sum_q (a - b)^2 / ell_q^2)."""
    inv_l2 = jnp.exp(-2.0 * hyp["log_ell"])
    return jnp.exp(hyp["log_sf2"]) * jnp.exp(-0.5 * sq_dist(a, b, inv_l2))


def collapsed_bound(hyp, z, stats, d: int, jitter: float):
    """``stats``: dict with A, B, C (m, d), D (m, m), KL and n."""
    m = z.shape[0]
    beta = jnp.exp(hyp["log_beta"])
    eye = jnp.eye(m, dtype=z.dtype)
    kmm = se_ard(hyp, z, z) + (jitter * jnp.exp(hyp["log_sf2"]) + 1e-12) * eye
    sigma = kmm + beta * stats["D"]
    lk = jnp.linalg.cholesky(kmm)
    ls = jnp.linalg.cholesky(sigma)
    logdet_k = 2.0 * jnp.sum(jnp.log(jnp.diagonal(lk)))
    logdet_s = 2.0 * jnp.sum(jnp.log(jnp.diagonal(ls)))
    kinv_d = jsl.cho_solve((lk, True), stats["D"])
    sinv_c = jsl.cho_solve((ls, True), stats["C"])
    n = stats["n"]
    return (-0.5 * n * d * jnp.log(2.0 * jnp.pi)
            + 0.5 * n * d * hyp["log_beta"]
            + 0.5 * d * (logdet_k - logdet_s)
            - 0.5 * beta * stats["A"]
            - 0.5 * beta * d * stats["B"]
            + 0.5 * beta * d * jnp.trace(kinv_d)
            + 0.5 * beta ** 2 * jnp.sum(stats["C"] * sinv_c)
            - stats["KL"])
