"""The collapsed variational bound (paper eq. 3.3) and the optimal q(u).

Given the reduced statistics (A, B, C, D, KL) and the inducing inputs Z the
bound is a function of constant-size quantities only:

  log p(Y) >= -nd/2 log 2pi + nd/2 log beta + d/2 log|Kmm| - d/2 log|Kmm+bD|
              - b/2 A - bd/2 B + bd/2 Tr(Kmm^-1 D)
              + b^2/2 Tr(C^T (Kmm + bD)^-1 C) - KL

Numerically we follow the Cholesky-whitened form used by GPy/GPflow: with
L = chol(Kmm) and Bmat = I + b L^-1 D L^-T,

  d/2 log|Kmm| - d/2 log|Kmm + bD| = -d/2 log|Bmat|
  Tr(C^T (Kmm+bD)^-1 C)            = || LB^-1 L^-1 C ||_F^2
  Tr(Kmm^-1 D)                      = sum((L^-1 D L^-T) diag)

which keeps everything PSD-safe under optimisation. The optimal variational
distribution over inducing values (derived analytically in the paper's
supplement) is

  q*(u) = N(u; b Kmm Sigma^-1 C,  Kmm Sigma^-1 Kmm),   Sigma = Kmm + b D

and the predictive posterior at X* follows the standard SGPR form.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from . import covariance as cov
from .stats import Stats

Array = jax.Array

DEFAULT_JITTER = 1e-6


def _chol_kmm(hyp: dict, z: Array, jitter: float,
              kernel: "cov.Kernel | None" = None) -> Array:
    kernel = cov.as_kernel(kernel)
    m = z.shape[0]
    kmm = kernel.K(hyp, z, z)
    # Jitter scaled by the kernel's signal variance (unit-free).
    vs = kernel.variance_scale(hyp)
    return jnp.linalg.cholesky(kmm + (jitter * vs + 1e-12) * jnp.eye(m, dtype=z.dtype))


def _raise_indefinite():
    raise FloatingPointError(
        "Bmat = I + beta L^-1 D L^-T is not positive definite, so the "
        "collapsed bound is NaN.  The fused psi2 kernel sums D in float32, "
        "which cannot resolve D when Kmm is this ill-conditioned (long "
        "lengthscales relative to the inducing-point spacing); use "
        "kernel_backend='xla' (float64 D) or start from shorter lengthscales.")


def _guard_definite(LB: Array) -> None:
    """Raise ``FloatingPointError`` on the host, from inside the compiled
    program, when ``LB = chol(Bmat)`` failed (a failed Cholesky is NaN).
    The check is one ``lax.cond``; its host callback runs only on failure
    (programs that hold one are not written to the persistent cache)."""
    ok = jnp.all(jnp.isfinite(jnp.diagonal(LB)))
    jax.lax.cond(ok, lambda: None,
                 lambda: jax.debug.callback(_raise_indefinite))


def collapsed_bound(
    hyp: dict,
    z: Array,
    stats: Stats,
    d: int,
    jitter: float = DEFAULT_JITTER,
    kernel: "cov.Kernel | None" = None,
    guard_definite: bool = False,
) -> Array:
    """Paper eq. 3.3 from reduced statistics. Returns a scalar lower bound.

    ``guard_definite``: raise (``FloatingPointError``, surfaced by JAX at
    the next sync) instead of returning NaN when ``Bmat`` is not positive
    definite — set by the engine when ``D`` comes from the float32 psi2
    kernel."""
    beta = jnp.exp(hyp["log_beta"])
    n = stats.n
    m = z.shape[0]
    L = _chol_kmm(hyp, z, jitter, kernel)

    # W = L^-1 D L^-T   (m, m)
    LiD = jsl.solve_triangular(L, stats.D, lower=True)
    W = jsl.solve_triangular(L, LiD.T, lower=True).T
    Bmat = jnp.eye(m, dtype=z.dtype) + beta * W
    LB = jnp.linalg.cholesky(Bmat)
    if guard_definite:
        _guard_definite(LB)

    # log|Bmat|
    logdet_b = 2.0 * jnp.sum(jnp.log(jnp.diagonal(LB)))
    # Tr(Kmm^-1 D)
    tr_kinv_d = jnp.trace(W)
    # c2 = LB^-1 L^-1 C  -> Tr(C^T Sigma^-1 C) = ||c2||^2 / ... :
    # Sigma = Kmm + bD = L Bmat L^T, Sigma^-1 = L^-T Bmat^-1 L^-1
    LiC = jsl.solve_triangular(L, stats.C, lower=True)      # (m, d)
    c2 = jsl.solve_triangular(LB, LiC, lower=True)          # (m, d)
    quad = jnp.sum(c2 * c2)

    return (
        -0.5 * n * d * jnp.log(2.0 * jnp.pi)
        + 0.5 * n * d * hyp["log_beta"]
        - 0.5 * d * logdet_b
        - 0.5 * beta * stats.A
        - 0.5 * beta * d * stats.B
        + 0.5 * beta * d * tr_kinv_d
        + 0.5 * beta**2 * quad
        - stats.KL
    )


class QU(NamedTuple):
    """Optimal q(u) = N(mean, cov) plus cached Cholesky factors for prediction."""

    mean: Array       # (m, d)
    cov: Array        # (m, m)
    L: Array          # chol(Kmm)
    LB: Array         # chol(I + b L^-1 D L^-T)
    c2: Array         # LB^-1 L^-1 C (whitened info vector)


def optimal_qu(hyp: dict, z: Array, stats: Stats, jitter: float = DEFAULT_JITTER,
               kernel: "cov.Kernel | None" = None) -> QU:
    """The analytically-optimal variational distribution over inducing values."""
    beta = jnp.exp(hyp["log_beta"])
    m = z.shape[0]
    L = _chol_kmm(hyp, z, jitter, kernel)
    LiD = jsl.solve_triangular(L, stats.D, lower=True)
    W = jsl.solve_triangular(L, LiD.T, lower=True).T
    Bmat = jnp.eye(m, dtype=z.dtype) + beta * W
    LB = jnp.linalg.cholesky(Bmat)
    LiC = jsl.solve_triangular(L, stats.C, lower=True)
    c2 = jsl.solve_triangular(LB, LiC, lower=True)          # (m, d)

    # mean = b Kmm Sigma^-1 C = b L LB^-T c2
    mean = beta * (L @ jsl.solve_triangular(LB.T, c2, lower=False))
    # cov = Kmm Sigma^-1 Kmm = (L LB^-T)(L LB^-T)^T
    half = jsl.solve_triangular(LB, L.T, lower=True).T      # L LB^-T : (m, m)
    cov = half @ half.T
    return QU(mean=mean, cov=cov, L=L, LB=LB, c2=c2)


def predict(
    hyp: dict,
    z: Array,
    qu: QU,
    xstar: Array,
    full_cov: bool = False,
    include_noise: bool = False,
    kernel: "cov.Kernel | None" = None,
) -> tuple[Array, Array]:
    """SGPR predictive posterior p(F*|Y) at inputs xstar (t, q).

    mean = b K*m Sigma^-1 C ; var = k** - K*m (Kmm^-1 - Sigma^-1) Km*.
    Returns (mean (t,d), var (t,) or cov (t,t)).
    """
    kernel = cov.as_kernel(kernel)
    beta = jnp.exp(hyp["log_beta"])
    ksm = kernel.K(hyp, xstar, z)                            # (t, m)
    a1 = jsl.solve_triangular(qu.L, ksm.T, lower=True)       # L^-1 Km*
    a2 = jsl.solve_triangular(qu.LB, a1, lower=True)         # LB^-1 L^-1 Km*
    mean = beta * (a2.T @ qu.c2)                             # (t, d)

    if full_cov:
        kss = kernel.K(hyp, xstar, xstar)
        covm = kss - a1.T @ a1 + a2.T @ a2
        if include_noise:
            covm = covm + jnp.eye(xstar.shape[0], dtype=covm.dtype) / beta
        return mean, covm
    kss = kernel.kdiag(hyp, xstar)
    var = kss - jnp.sum(a1 * a1, axis=0) + jnp.sum(a2 * a2, axis=0)
    if include_noise:
        var = var + 1.0 / beta
    return mean, var
