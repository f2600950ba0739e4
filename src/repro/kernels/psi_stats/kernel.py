"""Pallas TPU kernels for the psi-statistics map step — the paper's hot spot.

The paper's cost model for the map step is O(n m^2 q) elementwise work per
data shard (their §2.1/§3.2). A mechanical port would materialise the
(n, m, m, q) broadcast in HBM; these kernels stream it through VMEM.

psi2 (per point i and inducing pair p = (a, b), a <= b):

    psi2_ab = sf4 exp(static_ab) sum_i c_i exp(-E_ip),
    E_ip = sum_q (mu_iq - zbar_pq)^2 / den_iq,   zbar = (z_a + z_b) / 2,
    den = ell^2 + 2 s,   c_i = w_i prod_q (ell_q^2 / den_iq)^(1/2).

The bound multiplies psi2's per-entry error by about 1 / lambda_min(Kmm),
1e5 at a k-means start, and an exact psi2 is nearly singular along the
same directions as Kmm, which independent f32 rounding per entry breaks.
So the kernel sums S_p = sum_i c_i exp(-E_ip) to about 1e-13 relative in
compensated f32, each value an unevaluated pair hi + lo:

* the differences are exact: ops.py cuts mu and zbar into limbs on one
  power-of-two grid (v = v1 + v2 + v3, v1 and v2 with 12 bits each), so
  (mu1 - zb1) + (mu2 - zb2) is an f32 with no rounding and
  mu3 - zb3 is below 2^-44 of the range;
* the square, the product with 1/den and the sum over q are error-free
  transformations (Dekker's product, Knuth's TwoSum) -- no expanded
  square, so no cancellation;
* ``_exp_dd``: exp of a pair to about 1e-13;
* the weighted terms are summed with TwoSum into (8, block_p) hi and lo
  accumulators; ops.py folds their sublanes in f64 and applies the i-free
  factor sf4 * exp(static) in f64.

The grid is (pair tiles, row tiles); a step walks its rows eight at a time
(one sublane tile) with the pairs on the lanes.  Per row the kernel reads
the columns ``[mu1, mu2, mu3, inv_hi, inv_lo] x q, c_hi, c_lo``; per pair
the rows ``[zb1, zb2, zb3] x q``.

psi1 uses the expanded exponent one order lower:
  E = alpha_i + (mu/den) z^T - (1/(2 den)) (z^2)^T, two MXU matmuls.

Tiling contract (enforced/padded by ops.py): n % block_n == 0 and
block_n % 8 == 0; the pair count a multiple of block_p, on the TPU a
multiple of 128 (lane-dense rows).  Padded rows carry c = 0; padded pairs
are sliced off.  psi1 pads q NEUTRALLY: padded dims carry mu=s=z=0,
ell2=1, which contribute exactly 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .._common import block_spec, dot_nt

# Rows per step of a psi2 block's loop: one sublane tile.
PSI2_ROWS = 8


# ---------------------------------------------------------------------------
# Compensated f32 arithmetic: a value is an unevaluated pair hi + lo.
# ---------------------------------------------------------------------------

_SPLIT = 4097.0                  # 2^12 + 1: Veltkamp's split into 12 bits


def _two_sum(a, b):
    """``a + b`` as ``s + e`` exactly (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):
    """``a + b`` as ``s + e`` exactly, for ``|a| >= |b|`` (Dekker)."""
    s = a + b
    return s, b - (s - a)


def _split(a):
    c = _SPLIT * a
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b, b_split):
    """``a * b`` as ``p + e`` exactly (Dekker); ``b_split = _split(b)``."""
    a1, a2 = _split(a)
    b1, b2 = b_split
    p = a * b
    return p, ((a1 * b1 - p) + a1 * b2 + a2 * b1) + a2 * b2


def _two_sqr(a):
    a1, a2 = _split(a)
    p = a * a
    return p, ((a1 * a1 - p) + 2.0 * (a1 * a2)) + a2 * a2


_LOG2E = 1.4426950408889634
# ln 2 in three parts: k * _LN2_A and k * _LN2_B are exact for |k| < 256.
_LN2_A, _LN2_B = 0.693359375, -56961.0 / 2.0 ** 28
_LN2_C = 1.820635975313678e-09
# 1 / j! for j <= 5 as f32 pairs (hi in column 2j, lo in 2j + 1, down
# every sublane), for the compensated Horner steps.  The kernel reads them
# from an operand behind an optimization barrier: as constants, XLA (the
# interpret mode) would fold ``(c + h) - c`` to ``h``, losing the rounding
# error that the step keeps.
INV_FACT_DD = np.zeros((PSI2_ROWS, 128), np.float32)
INV_FACT_DD[:, :12] = [1.0, 0.0, 1.0, 0.0, 0.5, 0.0,
                       0.1666666716337204, -4.967053879312289e-09,
                       0.0416666679084301, -1.2417634698280722e-09,
                       0.008333333767950535, -4.34617203337595e-10]


def _exp_dd(xh, xl, inv_fact):
    """``exp(xh + xl)`` as an f32 pair, to about 1e-13 relative, for
    ``xh + xl <= 0`` with ``|xl| <= ulp(xh)``; 0 below -87.  ``inv_fact``:
    ``[(hi, lo) of 1 / j! for j = 0..5]``, ``INV_FACT_DD``'s values.

    ``x = k ln2 + r``, ``|r| <= ln2 / 2``, r kept as a pair; ``exp(r)``
    by its Taylor series to degree 11, the terms from r^6 on in f32 (their
    error is below 1e-13 of the sum) and the last six Horner steps in
    pairs; ``2^k`` built in the exponent bits."""
    k = jnp.floor(xh * _LOG2E + 0.5)
    r1 = xh - k * _LN2_A                                      # exact
    rh, rl = _two_sum(r1, -(k * _LN2_B))
    rh, rl = _fast_two_sum(rh, rl + (xl - k * _LN2_C))
    p = 1.0 / math.factorial(11)
    for j in range(10, 5, -1):
        p = p * rh + 1.0 / math.factorial(j)
    ph, pl_ = p, jnp.zeros_like(p)
    r_split = _split(rh)
    for ch, cl in reversed(inv_fact):
        h, e = _two_prod(ph, rh, r_split)
        s = ch + h
        e = (h - (s - ch)) + (e + (ph * rl + pl_ * rh)) + cl
        ph, pl_ = _fast_two_sum(s, e)
    ki = jnp.maximum(k, -126.0).astype(jnp.int32)
    two_k = lax.bitcast_convert_type(
        lax.shift_left(ki + 127, jnp.int32(23)), jnp.float32)
    keep = xh >= -87.0
    return (jnp.where(keep, ph * two_k, 0.0),
            jnp.where(keep, pl_ * two_k, 0.0))


# ---------------------------------------------------------------------------
# psi2: S_p = sum_i c_i exp(-E_ip) — grid (pair_tiles, n_tiles)
# ---------------------------------------------------------------------------

def _psi2_kernel(fact_ref, zp_ref, rows_ref, hi_ref, lo_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    q = zp_ref.shape[0] // 3

    def zp(j):                                                  # (1, bp)
        return zp_ref[j:j + 1, :]

    def rows(g, carry):
        acc_h, acc_l = carry
        at = pl.multiple_of(g * jnp.int32(PSI2_ROWS), PSI2_ROWS)
        r = rows_ref[pl.ds(at, PSI2_ROWS), :]                   # (8, 5q+2)

        def col(c, of=r):                                       # (8, 1)
            return of[:, c:c + 1]

        f = fact_ref[...]
        inv_fact = [(col(2 * j, f), col(2 * j + 1, f)) for j in range(6)]

        for j in range(q):
            dh = (col(j) - zp(j)) + (col(q + j) - zp(q + j))    # exact
            dh, dl = _fast_two_sum(dh, col(2 * q + j) - zp(2 * q + j))
            sh, sl = _two_sqr(dh)
            sl = sl + 2.0 * (dh * dl)
            ih, il = col(3 * q + j), col(4 * q + j)
            th, tl = _two_prod(sh, ih, _split(ih))
            tl = tl + (sh * il + sl * ih)
            if j == 0:
                eh, el = th, tl
            else:
                eh, err = _two_sum(eh, th)
                el = el + (err + tl)
        eh, el = _fast_two_sum(eh, el)
        ph, pl_ = _exp_dd(-eh, -el, inv_fact)
        ch, cl = col(5 * q), col(5 * q + 1)
        th, tl = _two_prod(ph, ch, _split(ch))
        tl = tl + (ph * cl + pl_ * ch)
        acc_h, err = _two_sum(acc_h, th)
        return _fast_two_sum(acc_h, acc_l + (err + tl))

    hi, lo = lax.fori_loop(jnp.int32(0),
                           jnp.int32(rows_ref.shape[0] // PSI2_ROWS), rows,
                           (hi_ref[...], lo_ref[...]))
    hi_ref[...] = hi
    lo_ref[...] = lo


def psi2_pallas(zp, rows, *, block_n=256, block_p=256, interpret=False):
    """Compensated sums ``S_p = hi + lo``, each (8, P) f32, one row per
    sublane of the rows' loop (ops.py folds them).  Inputs pre-padded and
    pre-shaped by ops.py.

    zp: (3q, P) pair limbs; rows: (n, 5q + 2) per-row columns; f32.
    """
    n, k = rows.shape
    q3, p = zp.shape
    assert n % block_n == 0 and block_n % PSI2_ROWS == 0
    assert p % block_p == 0
    acc = jax.ShapeDtypeStruct((PSI2_ROWS, p), jnp.float32)
    out = block_spec((PSI2_ROWS, block_p), lambda j, i: (0, j))
    return pl.pallas_call(
        _psi2_kernel,
        grid=(p // block_p, n // block_n),
        in_specs=[
            block_spec(INV_FACT_DD.shape, lambda j, i: (0, 0)),
            block_spec((q3, block_p), lambda j, i: (0, j)),
            block_spec((block_n, k), lambda j, i: (i, 0)),
        ],
        out_specs=[out, out],
        out_shape=[acc, acc],
        interpret=interpret,
    )(lax.optimization_barrier(jnp.asarray(INV_FACT_DD)), zp, rows)


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
