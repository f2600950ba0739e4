"""Pallas TPU kernels for the psi-statistics map step — the paper's hot spot.

The paper's cost model for the map step is O(n m^2 q) elementwise work per
data shard (their §2.1/§3.2). A mechanical port would materialise the
(n, m, m, q) broadcast in HBM; this kernel streams it through VMEM one
(block_n, block_m) slab at a time:

  psi2 exponent (per point i, inducing pair (a,b), latent dim q):
    E[i,ab] = static[ab] + lognorm_i - sum_q (mu_iq - zbar_abq)^2 / den_iq
    with den_iq = ell_q^2 + 2 s_iq, zbar = (z_a + z_b)/2.
  Expanding the square separates i from (a,b):
    E[i,ab] - static[ab] = alpha_i + M_i . Zb_ab,
    M = [2 mu/den, -1/den] (n, 2q),  Zb_ab = [zbar_ab, zbar_ab^2] (2q,),
    alpha_i = lognorm_i - sum_q mu_iq^2 / den_iq.
  The kernel sums S_ab = sum_i w_i exp(alpha_i + M_i . Zb_ab) one inducing
  row a at a time: one (block_n, 2q) x (block_m, 2q)^T MXU matmul, the
  exponential and a weighted sublane reduce into one lane row, so the live
  slab is (block_n, block_m).  ops.py computes M, alpha and Zb in the
  caller's f64 and rounds each once to f32, and applies the i-free factor
  sf4 * exp(static_ab) in f64.  The exponential is ``_common.exp_f32``:
  the USPS bound amplifies psi2 errors ~30x, and the TPU's own f32 exp is
  off by up to 5.6e-6.

psi1 uses the same expansion one order lower:
  E = alpha_i + (mu/den) z^T - (1/(2 den)) (z^2)^T, two MXU matmuls.

Tiling contract (enforced/padded by ops.py):
  n % block_n == 0, m % block_m == 0, block_m % PSI2_ROWS == 0; on the TPU
  block_m is a multiple of 128 (lane-aligned output rows).  psi2 needs no
  latent padding (its 2q axis is whole in every block).  psi1 pads q
  NEUTRALLY: padded dims carry mu=s=z=0, ell2=1, which contribute
  exactly 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._common import block_spec, dot_nt, exp_f32

# Inducing rows (a) per psi2 grid step: one sublane tile.
PSI2_ROWS = 8


# ---------------------------------------------------------------------------
# psi2: S = sum_i w_i exp(E_iab - static_ab) — grid (a_tiles, b_tiles, n_tiles)
# ---------------------------------------------------------------------------

def _psi2_kernel(zb_ref, m_ref, alpha_ref, w_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    mm = m_ref[...]                                               # (bn, 2q)
    alpha = alpha_ref[...]                                        # (bn, 1)
    w = w_ref[...]                                                # (bn, 1)
    for r in range(out_ref.shape[0]):
        e = alpha + dot_nt(mm, zb_ref[r])                         # (bn, bm)
        out_ref[r:r + 1, :] += jnp.sum(w * exp_f32(e), axis=0,
                                       keepdims=True)


def psi2_pallas(zb, mmat, alpha, w, *, block_n=128, block_m=128,
                interpret=False):
    """Unscaled w-weighted psi2 sums S (m, m). All inputs pre-padded and
    pre-shaped (see ops.py, which applies ``sf4 * exp(static)``).

    zb: (m, m, 2q); mmat: (n, 2q); alpha, w: (n, 1); all f32.
    """
    n, q2 = mmat.shape
    m = zb.shape[0]
    rows = PSI2_ROWS
    assert n % block_n == 0 and m % block_m == 0 and m % rows == 0
    grid = (m // rows, m // block_m, n // block_n)
    return pl.pallas_call(
        _psi2_kernel,
        grid=grid,
        in_specs=[
            block_spec((rows, block_m, q2), lambda a, b, k: (a, b, 0)),  # Zb
            block_spec((block_n, q2), lambda a, b, k: (k, 0)),           # M
            block_spec((block_n, 1), lambda a, b, k: (k, 0)),        # alpha
            block_spec((block_n, 1), lambda a, b, k: (k, 0)),            # w
        ],
        out_specs=block_spec((rows, block_m), lambda a, b, k: (a, b)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        interpret=interpret,
    )(zb, mmat, alpha, w)


# ---------------------------------------------------------------------------
# psi1: (n, m) <K_im> — grid (n_tiles, m_tiles)
# ---------------------------------------------------------------------------

def _psi1_kernel(ell2_ref, sf2_ref, z_ref, mu_ref, s_ref, out_ref):
    ell2 = ell2_ref[...]                        # (1, q)
    mu = mu_ref[...]                            # (bn, q)
    s = s_ref[...]                              # (bn, q)
    z = z_ref[...]                              # (bm, q)

    den = ell2 + s
    inv_den = 1.0 / den
    lognorm = -0.5 * jnp.sum(jnp.log(den) - jnp.log(ell2), axis=1,
                             keepdims=True)
    mdn = mu * inv_den
    alpha = lognorm - 0.5 * jnp.sum(mu * mdn, axis=1, keepdims=True)
    e = alpha + dot_nt(mdn, z) - dot_nt(0.5 * inv_den, z * z)       # (bn, bm)
    out_ref[...] = sf2_ref[...] * jnp.exp(e)


def psi1_pallas(ell2, sf2, z, mu, s, *, block_n=256, block_m=128,
                interpret=False):
    """Psi1 (n, m). Inputs pre-padded."""
    n, q = mu.shape
    m = z.shape[0]
    assert n % block_n == 0 and m % block_m == 0
    grid = (n // block_n, m // block_m)
    return pl.pallas_call(
        _psi1_kernel,
        grid=grid,
        in_specs=[
            block_spec((1, q), lambda i, j: (0, 0)),
            block_spec((1, 1), lambda i, j: (0, 0)),
            block_spec((block_m, q), lambda i, j: (j, 0)),
            block_spec((block_n, q), lambda i, j: (i, 0)),
            block_spec((block_n, q), lambda i, j: (i, 0)),
        ],
        out_specs=block_spec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(ell2, sf2, z, mu, s)
