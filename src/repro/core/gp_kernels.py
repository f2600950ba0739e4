"""SE-ARD covariance and its closed-form psi statistics.

The paper (and Titsias & Lawrence 2010) use a squared-exponential ARD kernel

    k(x, x') = sf2 * exp(-0.5 * sum_q (x_q - x'_q)^2 / ell_q^2)

Under a diagonal Gaussian ``q(X_i) = N(mu_i, diag(S_i))`` over latent inputs
the kernel expectations against q — the "psi statistics" — are analytic:

    psi0_i       = <k(x_i, x_i)>_q            (scalar per point)
    Psi1[i, m]   = <k(x_i, z_m)>_q            (n x m)
    psi2_i[m,m'] = <k(x_i, z_m) k(x_i, z_m')>_q   (m x m per point)

Setting S_i = 0, mu_i = X_i recovers plain kernel evaluations — that is the
paper's unifying view of sparse GP regression as a zero-variance GPLVM.

Hyper-parameters are carried in log-space for unconstrained optimisation:
``hyp = {"log_sf2": (), "log_ell": (q,), "log_beta": ()}``.

The canonical names are now ``se_kernel`` / ``se_kdiag`` / ``se_psi0`` /
``se_psi1`` / ``se_psi2`` — the SE-ARD entry of the compositional kernel
layer (``core.covariance``).  The old ``ard_*`` / bare ``psi*`` names remain
as thin deprecation wrappers so existing code, tests, and checkpoints keep
working unchanged.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(old: str, new: str) -> None:
    if old in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(old)
    warnings.warn(
        f"repro.core.gp_kernels.{old} is deprecated; use "
        f"gp_kernels.{new} or a covariance.SEARD kernel expression",
        DeprecationWarning, stacklevel=3)


def sqdist(a: Array, b: Array) -> Array:
    """Pairwise squared distances between rows of ``a`` (n,q) and ``b`` (m,q).

    Computed in the input dtype via the expanded form — but *symmetrised*
    first: both operands are shifted by a common (gradient-stopped) anchor
    before expanding.  Squared distances are shift-invariant, and the shift
    removes the catastrophic cancellation the raw ``a²+b²-2ab`` form suffers
    for large-magnitude inputs (offset 1e4 ⇒ a²≈1e8, so f64 rounding of the
    cross term swamps O(1) distances).  Clamped after expansion: the form
    can still go slightly negative in floating point.

    The anchor is ``b``'s first row — NOT a batch mean — so each output row
    depends only on its own inputs: row-locality keeps chunked stats
    bitwise-equal to monolithic ones and padded serving batches
    bitwise-equal to unpadded ones (pad rows must never leak).
    """
    c = (jax.lax.stop_gradient(b[0]) if b.shape[0]
         else jnp.zeros(b.shape[-1:], b.dtype))
    ac = a - c
    bc = b - c
    a2 = jnp.sum(ac * ac, axis=-1)[:, None]
    b2 = jnp.sum(bc * bc, axis=-1)[None, :]
    return jnp.maximum(a2 + b2 - 2.0 * ac @ bc.T, 0.0)


def se_kernel(hyp: dict, a: Array, b: Array) -> Array:
    """K_ab for the SE-ARD kernel; a: (n,q), b: (m,q) -> (n,m)."""
    ell = jnp.exp(hyp["log_ell"])  # (q,)
    sf2 = jnp.exp(hyp["log_sf2"])
    return sf2 * jnp.exp(-0.5 * sqdist(a / ell, b / ell))


def se_kdiag(hyp: dict, a: Array) -> Array:
    """diag(K_aa) — constant sf2 for the SE kernel."""
    sf2 = jnp.exp(hyp["log_sf2"])
    return jnp.full(a.shape[:-1], sf2, dtype=a.dtype)


# ---------------------------------------------------------------------------
# Psi statistics (closed form, SE-ARD, diagonal Gaussian q(X))
# ---------------------------------------------------------------------------

def se_psi0(hyp: dict, mu: Array, s: Array) -> Array:
    """<k(x_i,x_i)> per point: (n,). For SE this is sf2 regardless of q(X)."""
    del s
    sf2 = jnp.exp(hyp["log_sf2"])
    return jnp.full(mu.shape[:-1], sf2, dtype=mu.dtype)


def se_psi1(hyp: dict, z: Array, mu: Array, s: Array) -> Array:
    """<k(x_i, z_m)>: (n, m).

    Psi1[i,m] = sf2 * prod_q (1 + S_iq/l_q^2)^(-1/2)
                    * exp(-0.5 (mu_iq - z_mq)^2 / (l_q^2 + S_iq))
    """
    ell2 = jnp.exp(2.0 * hyp["log_ell"])  # (q,)
    sf2 = jnp.exp(hyp["log_sf2"])
    denom = ell2[None, :] + s  # (n, q)
    # log-normaliser: -0.5 sum_q log(1 + S/l^2)
    lognorm = -0.5 * jnp.sum(jnp.log1p(s / ell2[None, :]), axis=-1)  # (n,)
    d = mu[:, None, :] - z[None, :, :]  # (n, m, q)
    expo = -0.5 * jnp.sum(d * d / denom[:, None, :], axis=-1)  # (n, m)
    return sf2 * jnp.exp(lognorm[:, None] + expo)


def se_psi2(hyp: dict, z: Array, mu: Array, s: Array) -> Array:
    """Sum_i <k(x_i,z_m) k(x_i,z_m')>: (m, m) — the D statistic of the paper.

    Per point:
      psi2_i[m,m'] = sf2^2 * prod_q (1 + 2 S_iq/l_q^2)^(-1/2)
          * exp(-(z_mq - z_m'q)^2 / (4 l_q^2) - (mu_iq - zbar_q)^2 / (l_q^2 + 2 S_iq))
      with zbar = (z_m + z_m') / 2.
    """
    return jnp.sum(psi2_per_point(hyp, z, mu, s), axis=0)


# ---------------------------------------------------------------------------
# Deprecated aliases (pre-compositional-kernel API; warn once, then delegate)
# ---------------------------------------------------------------------------

def ard_kernel(hyp: dict, a: Array, b: Array) -> Array:
    """Deprecated alias of :func:`se_kernel`."""
    _warn_deprecated("ard_kernel", "se_kernel")
    return se_kernel(hyp, a, b)


def ard_kdiag(hyp: dict, a: Array) -> Array:
    """Deprecated alias of :func:`se_kdiag`."""
    _warn_deprecated("ard_kdiag", "se_kdiag")
    return se_kdiag(hyp, a)


def psi0(hyp: dict, mu: Array, s: Array) -> Array:
    """Deprecated alias of :func:`se_psi0`."""
    _warn_deprecated("psi0", "se_psi0")
    return se_psi0(hyp, mu, s)


def psi1(hyp: dict, z: Array, mu: Array, s: Array) -> Array:
    """Deprecated alias of :func:`se_psi1`."""
    _warn_deprecated("psi1", "se_psi1")
    return se_psi1(hyp, z, mu, s)


def psi2(hyp: dict, z: Array, mu: Array, s: Array) -> Array:
    """Deprecated alias of :func:`se_psi2`."""
    _warn_deprecated("psi2", "se_psi2")
    return se_psi2(hyp, z, mu, s)


def psi2_per_point(hyp: dict, z: Array, mu: Array, s: Array) -> Array:
    """(n, m, m) un-summed psi2 — used by tests and the per-point oracle."""
    ell2 = jnp.exp(2.0 * hyp["log_ell"])  # (q,)
    sf2 = jnp.exp(hyp["log_sf2"])
    n, q = mu.shape
    # Static term: -(z_m - z_m')^2 / (4 l^2), summed over q -> (m, m)
    dz = z[:, None, :] - z[None, :, :]
    static = -0.25 * jnp.sum(dz * dz / ell2, axis=-1)  # (m, m)
    zbar = 0.5 * (z[:, None, :] + z[None, :, :])  # (m, m, q)
    denom = ell2[None, :] + 2.0 * s  # (n, q)
    lognorm = -0.5 * jnp.sum(jnp.log1p(2.0 * s / ell2[None, :]), axis=-1)  # (n,)
    d = mu[:, None, None, :] - zbar[None, :, :, :]  # (n, m, m, q)
    expo = -jnp.sum(d * d / denom[:, None, None, :], axis=-1)  # (n, m, m)
    return (sf2 * sf2) * jnp.exp(lognorm[:, None, None] + static[None] + expo)


def psi2_chunked(hyp: dict, z: Array, mu: Array, s: Array, chunk: int = 256) -> Array:
    """Memory-bounded psi2: fold over n in chunks of ``chunk`` (static shapes).

    Materialising the (n, m, m, q) broadcast in :func:`psi2_per_point` is the
    naive formulation the paper ascribes O(n m^2 q) cost to; this streams it.
    """
    n = mu.shape[0]
    pad = (-n) % chunk
    mu_p = jnp.pad(mu, ((0, pad), (0, 0)))
    # Pad S with ones (any positive value) and mask via a weight vector.
    s_p = jnp.pad(s, ((0, pad), (0, 0)), constant_values=1.0)
    w = jnp.pad(jnp.ones((n,), mu.dtype), (0, pad))
    nb = mu_p.shape[0] // chunk
    mu_b = mu_p.reshape(nb, chunk, -1)
    s_b = s_p.reshape(nb, chunk, -1)
    w_b = w.reshape(nb, chunk)

    def body(acc, args):
        mu_c, s_c, w_c = args
        p = psi2_per_point(hyp, z, mu_c, s_c)  # (chunk, m, m)
        return acc + jnp.einsum("c,cab->ab", w_c, p), None

    m = z.shape[0]
    init = jnp.zeros((m, m), mu.dtype)
    acc, _ = jax.lax.scan(body, init, (mu_b, s_b, w_b))
    return acc


def kl_to_standard_normal(mu: Array, s: Array) -> Array:
    """Sum_i KL(N(mu_i, diag(S_i)) || N(0, I)) — the paper's KL term."""
    return 0.5 * jnp.sum(s + mu * mu - jnp.log(s) - 1.0)


def psi2_mxu(hyp: dict, z: Array, mu: Array, s: Array, w: Array,
             chunk: int = 1024) -> Array:
    """Beyond-paper psi2: the MXU-matmul reformulation (see
    kernels/psi_stats) expressed in pure jnp — the exponent decouples data
    from inducing pairs as E = alpha_i + M_i . Zb_ab, so the O(n m^2 q)
    work becomes two (chunk x 2q) @ (2q x P) matmuls + exp + one
    (1 x chunk) @ (chunk x P) reduce per chunk. Same O() flops, MXU-
    instead of VPU-bound, and never materialises (n, m, m, q).  Psi2 is
    symmetric, so only the P = m (m + 1) / 2 pairs a <= b are computed and
    mirrored (:func:`from_pairs`).
    """
    ell2 = jnp.exp(2.0 * hyp["log_ell"])
    sf4 = jnp.exp(2.0 * hyp["log_sf2"])
    m, q = z.shape
    n = mu.shape[0]
    a, b = np.triu_indices(m)
    zbar = 0.5 * (z[a] + z[b])                                   # (P, q)
    zb_mat = jnp.concatenate([zbar, zbar * zbar], -1).T          # (2q, P)
    dz = z[a] - z[b]
    static = -0.25 * jnp.sum(dz * dz / ell2, -1)                 # (P,)

    pad = (-n) % chunk
    mu_p = jnp.pad(mu, ((0, pad), (0, 0)))
    s_p = jnp.pad(s, ((0, pad), (0, 0)), constant_values=1.0)
    w_p = jnp.pad(w, (0, pad))
    nb = mu_p.shape[0] // chunk
    mu_b = mu_p.reshape(nb, chunk, q)
    s_b = s_p.reshape(nb, chunk, q)
    w_b = w_p.reshape(nb, chunk)

    def body(acc, args):
        mu_c, s_c, w_c = args
        den = ell2[None, :] + 2.0 * s_c
        inv = 1.0 / den
        lognorm = -0.5 * jnp.sum(jnp.log(den / ell2[None, :]), 1)
        alpha = lognorm - jnp.sum(mu_c * mu_c * inv, 1)          # (chunk,)
        m_mat = jnp.concatenate([2.0 * mu_c * inv, -inv], 1)     # (chunk,2q)
        e = alpha[:, None] + m_mat @ zb_mat + static
        return acc + w_c @ jnp.exp(e), None

    acc, _ = jax.lax.scan(body, jnp.zeros(a.shape, mu.dtype),
                          (mu_b, s_b, w_b))
    return from_pairs(sf4 * acc, m)


def from_pairs(p: Array, m: int) -> Array:
    """The symmetric (m, m) matrix whose pairs a <= b, in
    ``np.triu_indices(m)`` order, are ``p``: one scatter to distinct
    entries and a select, so its VJP is a gather (each pair's two
    cotangents summed).  On a TPU v5e a scatter of the 11,325 pairs of
    m=150 takes ~1 ms, and a gather's VJP is such a scatter."""
    a, b = np.triu_indices(m)
    upper = jnp.zeros((m, m), p.dtype).at[a, b].set(p, unique_indices=True)
    return jnp.where(np.triu(np.ones((m, m), bool)), upper, upper.T)
