"""The bound's statistics, summed block by block over the rows.

Regression (``s is None``): K = k(X, Z), and per row psi0 = sf2,
psi1 = K, psi2 = K^T K.  GPLVM, with q(x_n) = N(mu_n, diag s_n), the
closed-form SE-ARD expectations (Titsias & Lawrence 2010):

  psi1[n, j]    = sf2 prod_q (1 + s/l2)^-1/2 exp(-(mu - z_j)^2 / (2 (l2 + s)))
  psi2[n, i, j] = sf2^2 prod_q (1 + 2 s/l2)^-1/2
                  exp(-(z_i - z_j)^2 / (4 l2) - (mu - zbar_ij)^2 / (l2 + 2 s))
  KL[n]         = 1/2 sum_q (s + mu^2 - log s - 1)

with zbar_ij = (z_i + z_j) / 2; the square in psi2's exponent is expanded
over q into matrix products, as in ``bound.sq_dist``.  Every statistic is
a weighted sum over rows, so a block scan whose blocks are recomputed in
the backward pass (``jax.checkpoint``) holds one block's worth for any n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .bound import se_ard, sq_dist


def _psi_block(hyp, z, mu, s):
    l2 = jnp.exp(2.0 * hyp["log_ell"])
    sf2 = jnp.exp(hyp["log_sf2"])
    m, q = z.shape
    # psi1: per row n, the scaled distance with per-row lengthscales l2 + s.
    inv1 = 1.0 / (l2 + s)                                       # (b, q)
    d1 = (jnp.sum(mu * mu * inv1, -1)[:, None] - 2.0 * (mu * inv1) @ z.T
          + inv1 @ (z * z).T)                                   # (b, m)
    psi1 = sf2 * jnp.exp(-0.5 * jnp.sum(jnp.log1p(s / l2), -1)[:, None]
                         - 0.5 * d1)
    # psi2: (mu - zbar)^2 / (l2 + 2 s) summed over q, as products.
    inv2 = 1.0 / (l2 + 2.0 * s)                                 # (b, q)
    zbar = (0.5 * (z[:, None, :] + z[None, :, :])).reshape(m * m, q)
    d2 = (jnp.sum(mu * mu * inv2, -1)[:, None] - 2.0 * (mu * inv2) @ zbar.T
          + inv2 @ (zbar * zbar).T)                             # (b, m*m)
    dz = 0.25 * sq_dist(z, z, 1.0 / l2).reshape(m * m)
    e2 = (-0.5 * jnp.sum(jnp.log1p(2.0 * s / l2), -1)[:, None]
          - dz[None, :] - d2)
    return psi1, (sf2 * sf2 * jnp.exp(e2)).reshape(-1, m, m)


def block_stats(hyp, z, y, x, s, w):
    """Statistics of one block of rows; ``x`` holds the inputs (the means
    mu in the GPLVM), ``w`` (b,) weights each row."""
    a = jnp.sum(w * jnp.sum(y * y, -1))
    b = jnp.sum(w) * jnp.exp(hyp["log_sf2"])
    if s is None:
        k = se_ard(hyp, x, z)
        c = k.T @ (w[:, None] * y)
        dd = (k * w[:, None]).T @ k
        kl = jnp.zeros((), y.dtype)
    else:
        psi1, psi2 = _psi_block(hyp, z, x, s)
        c = psi1.T @ (w[:, None] * y)
        dd = jnp.einsum("n,nij->ij", w, psi2)
        kl = 0.5 * jnp.sum(w * jnp.sum(s + x * x - jnp.log(s) - 1.0, -1))
    return {"A": a, "B": b, "C": c, "D": dd, "KL": kl, "n": jnp.sum(w)}


def summed_stats(hyp, z, y, x, s, w, block: int):
    """``block_stats`` summed over blocks of ``block`` rows (rows padded
    with zero weight up to a whole block)."""
    pad = (-y.shape[0]) % block

    def blocks(a, fill=0.0):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape((-1, block) + a.shape[1:])

    xs = (blocks(y), blocks(x), None if s is None else blocks(s, 1.0),
          blocks(w))

    @jax.checkpoint
    def body(acc, blk):
        return jax.tree.map(jnp.add, acc, block_stats(hyp, z, *blk)), None

    shapes = jax.eval_shape(block_stats, hyp, z,
                            *(None if a is None else a[0] for a in xs))
    zero = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), shapes)
    return jax.lax.scan(body, zero, xs)[0]
