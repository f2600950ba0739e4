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

psi2's backward kernel (``psi2_bwd_pallas``) has the same grid and layout
and computes each term's ``exp(-E_ip)`` with the forward's own code
(``_exp_neg_e``).  With G_p the pair's weight (its cotangent times
sf4 exp(static)) it sums, all in pairs: over the rows, into (8, block_p)
accumulators as the forward does, ``S_p = sum_i c_i e`` and
``sum_i c_i inv_iq e d_q`` (``d = mu - zbar``); over the pairs, per row,
``sum_p G e``, ``sum_p G e d_q`` and ``sum_p G e d_q^2``, whose lanes are
folded once per 8-row step by a compensated butterfly (``_lane_sums``).
Nothing of size rows x pairs leaves VMEM.

psi1 uses the expanded exponent one order lower:
  E = alpha_i + (mu/den) z^T - (1/(2 den)) (z^2)^T, two MXU matmuls.

Tiling contract (enforced/padded by ops.py): n % block_n == 0 and
block_n % 8 == 0; the pair count a multiple of block_p, on the TPU a
multiple of 128 (lane-dense rows).  Padded rows carry c = 0; padded pairs
are sliced off.  psi1 pads q NEUTRALLY: padded dims carry mu=s=z=0,
ell2=1, which contribute exactly 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _same(x):
    return x


def _split(a, hold=_same):
    c = hold(_SPLIT * a)
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b, b_split, hold=_same):
    """``a * b`` as ``p + e`` exactly (Dekker); ``b_split = _split(b)``."""
    a1, a2 = _split(a, hold)
    b1, b2 = b_split
    p = hold(a * b)
    return p, ((a1 * b1 - p) + a1 * b2 + a2 * b1) + a2 * b2


def _two_sqr(a, hold=_same):
    a1, a2 = _split(a, hold)
    p = hold(a * a)
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


def _exp_dd(xh, xl, inv_fact, hold=_same):
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
    r_split = _split(rh, hold)
    for ch, cl in reversed(inv_fact):
        h, e = _two_prod(ph, rh, r_split, hold)
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

def _inv_fact(fact_ref):
    f = fact_ref[...]
    return [(f[:, 2 * j:2 * j + 1], f[:, 2 * j + 1:2 * j + 2])
            for j in range(6)]


def _pair_diff(col, zp, q, j):
    """``mu - zbar`` on latent dim ``j`` as an f32 pair, from the limbs:
    the first two limbs' differences sum exactly, the third's is added by
    Dekker's sum.  ``col(c)``: the rows' column c (8, 1); ``zp(r)``: the
    pairs' row r (1, bp)."""
    dh = (col(j) - zp(j)) + (col(q + j) - zp(q + j))            # exact
    return _fast_two_sum(dh, col(2 * q + j) - zp(2 * q + j))


def _exp_neg_e(col, zp, q, inv_fact, hold=_same):
    """``exp(-E_ip)`` as an f32 pair, ``E = sum_q (mu - zbar)^2 inv`` with
    the square, the product with ``inv`` (a pair) and the sum over q
    error-free: the one computation of each term, shared by the forward
    and backward kernels so that both see the same values."""
    for j in range(q):
        dh, dl = _pair_diff(col, zp, q, j)
        sh, sl = _two_sqr(dh, hold)
        sl = sl + 2.0 * (dh * dl)
        ih, il = col(3 * q + j), col(4 * q + j)
        th, tl = _two_prod(sh, ih, _split(ih, hold), hold)
        tl = tl + (sh * il + sl * ih)
        if j == 0:
            eh, el = th, tl
        else:
            eh, err = _two_sum(eh, th)
            el = el + (err + tl)
    eh, el = _fast_two_sum(eh, el)
    return _exp_dd(-eh, -el, inv_fact, hold)


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

        def col(c):                                             # (8, 1)
            return r[:, c:c + 1]

        ph, pl_ = _exp_neg_e(col, zp, q, _inv_fact(fact_ref))
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
# psi2's backward: the sums its cotangents need — grid (pair_tiles, n_tiles)
# ---------------------------------------------------------------------------

LANES = 128


def _mul(a, a_split, b, b_split, hold):
    """The product of two f32 pairs to about 2^-46 relative: Dekker's
    product of the highs plus the cross terms; ``*_split = _split(hi)``."""
    (ah, al), (a1, a2), (bh, bl), (b1, b2) = a, a_split, b, b_split
    p = hold(ah * bh)
    e = ((a1 * b1 - p) + a1 * b2 + a2 * b1) + a2 * b2
    return p, e + (ah * bl + al * bh)


def _add(a, b):
    """The sum of two f32 pairs, TwoSum of the highs, renormalised.  Every
    sum of the backward kernel goes through it."""
    s, e = _two_sum(a[0], b[0])
    return _fast_two_sum(s, e + (a[1] + b[1]))


def _held(fact_ref):
    """Interpret mode's ``hold``: XLA's CPU backend fuses a product into a
    later sum (an FMA) where it sees one, which breaks the error-free
    transformations; an OR with a zero it cannot see (the last lane of
    ``INV_FACT_DD``) keeps every rounded product rounded.  The compiled
    kernel holds nothing, as the forward kernel does."""
    zero = lax.bitcast_convert_type(fact_ref[0, LANES - 1], jnp.int32)

    def hold(x):
        return lax.bitcast_convert_type(
            lax.bitcast_convert_type(x, jnp.int32) | zero, jnp.float32)

    return hold


def _roll(x, shift):
    """``x`` rotated ``shift`` lanes: lane j reads lane ``j - shift``."""
    return pltpu.roll(x, jnp.int32(shift % LANES), 1)


def _slots(k):
    """Where ``_lane_sums`` leaves each of ``k <= 64`` sums in its 128
    lanes: sum j's high part in lane ``slots[j] * width``, its low part in
    the next lane, with ``width = 128 >> levels``."""
    assert 1 <= k <= LANES // 2, k
    groups, slots, levels = [[j] for j in range(k)], [0] * k, 0
    while len(groups) > 1:
        half = (len(groups) + 1) // 2
        for i, g in enumerate(groups):
            for j in g:
                slots[j] = 2 * slots[j] + (i >= half)
        groups = [g + (groups[i + half] if i + half < len(groups) else [])
                  for i, g in enumerate(groups[:half])]
        levels += 1
    return [j * (LANES >> levels) for j in slots]


def row_sum_lanes(k):
    """Where the backward kernel leaves each of its ``k`` row sums in a row
    of its packed output, whose 128-lane blocks hold 64 sums each: sum j's
    high part in lane ``lanes[j]``, its low part in the next."""
    return np.concatenate([LANES * c + np.asarray(_slots(min(k - 64 * c, 64)))
                           for c in range(-(-k // 64))])


def _lane_sums(vals, lane):
    """Each (8, 128) pair of ``vals`` (at most 64) summed over its lanes,
    compensated, packed into one (8, 128) array as ``_slots`` says.

    A merge halves two vregs' lanes at once: at lane bit h the first's
    pairs ``(j, j + h)`` sum into the lanes with bit h clear and the
    second's into those with it set, so k sums take about k merges and
    ``log2 k`` levels, then ``log2(width)`` in-place halvings."""
    h = LANES // 2
    while len(vals) > 1:
        half = (len(vals) + 1) // 2
        up = (lane & h) != 0
        out = []
        for i, a in enumerate(vals[:half]):
            if i + half < len(vals):
                b = vals[i + half]
                x = tuple(jnp.where(up, v, u) for u, v in zip(a, b))
                y = tuple(jnp.where(up, _roll(t, h), _roll(t, -h))
                          for t in (jnp.where(up, u, v)
                                    for u, v in zip(a, b)))
            else:                       # alone: its set lanes hold no sum
                x, y = a, tuple(_roll(t, -h) for t in a)
            out.append(_add(x, y))
        vals, h = out, h // 2
    (x,) = vals
    width = 2 * h
    while h:
        x = _add(x, tuple(_roll(t, -h) for t in x))
        h //= 2
    return jnp.where((lane & (width - 1)) == 0, x[0], _roll(x[1], 1))


def _psi2_bwd_kernel(fact_ref, zp_ref, rows_ref, hi_ref, lo_ref, row_ref, *,
                     interpret):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    q = (zp_ref.shape[0] - 2) // 3
    nv = zp_ref.shape[1] // LANES
    lane = lax.broadcasted_iota(jnp.int32, (PSI2_ROWS, LANES), 1)
    hold = _held(fact_ref) if interpret else _same

    def split(x):
        return _split(x, hold)

    def mul(a, b, a_split=None, b_split=None):
        return _mul(a, a_split or split(a[0]), b, b_split or split(b[0]),
                    hold)

    def zp(j):                                                  # (1, bp)
        return zp_ref[j:j + 1, :]

    g = (zp(3 * q), zp(3 * q + 1))
    g_split = split(g[0])

    def accumulate(k, t):
        """Pair sum k (sublane block k of the outputs) += t, (8, bp)."""
        at = slice(PSI2_ROWS * k, PSI2_ROWS * (k + 1))
        hi_ref[at, :], lo_ref[at, :] = _add((hi_ref[at, :], lo_ref[at, :]),
                                            t)

    def fold(t):
        """(8, bp) pair -> (8, 128): the lane tiles summed."""
        tiles = [tuple(v[:, LANES * i:LANES * (i + 1)] for v in t)
                 for i in range(nv)]
        out = tiles[0]
        for tile in tiles[1:]:
            out = _add(out, tile)
        return out

    def rows(step, carry):
        at = pl.multiple_of(step * jnp.int32(PSI2_ROWS), PSI2_ROWS)
        r = rows_ref[pl.ds(at, PSI2_ROWS), :]                   # (8, 7q+2)

        def col(c):                                             # (8, 1)
            return r[:, c:c + 1]

        e = _exp_neg_e(col, zp, q, _inv_fact(fact_ref), hold)
        e_split = split(e[0])
        accumulate(0, mul(e, (col(5 * q), col(5 * q + 1)), e_split))  # S
        u = mul(e, g, e_split, g_split)                         # G e
        u_split = split(u[0])
        by_d, by_d2 = [], []
        for j in range(q):
            d = _pair_diff(col, zp, q, j)
            d_split = split(d[0])
            y = mul(e, d, e_split, d_split)                     # e d
            accumulate(1 + j, mul(y, (col(5 * q + 2 + j),       # c inv e d
                                      col(6 * q + 2 + j))))
            ud = mul(u, d, u_split, d_split)                    # G e d
            by_d.append(fold(ud))
            by_d2.append(fold(mul(ud, d, None, d_split)))       # G e d^2
        sums = [fold(u)] + by_d + by_d2
        for c in range(-(-len(sums) // 64)):            # 64 to a lane block
            row_ref[pl.ds(at, PSI2_ROWS), LANES * c:LANES * (c + 1)] = \
                _lane_sums(sums[64 * c:64 * (c + 1)], lane)
        return carry

    lax.fori_loop(jnp.int32(0), jnp.int32(rows_ref.shape[0] // PSI2_ROWS),
                  rows, jnp.int32(0))


def psi2_bwd_pallas(zp, rows, *, block_n, block_p, interpret=False):
    """The sums of psi2's cotangents, per row i and pair p, with
    ``e = exp(-E_ip)``, ``d = mu_i - zbar_p`` and G the pairs' weights:

    * over the rows, into (8, P) hi and lo accumulators per sum (``ops.py``
      folds their sublanes): ``S_p = sum_i c_i e`` (block 0) and
      ``Q_pq = sum_i c_i inv_iq e d_q`` (block 1 + q);
    * over the pairs, per row and pair tile: ``sum_p G e``,
      ``sum_p G e d_q`` and ``sum_p G e d_q^2``, packed by
      ``_lane_sums`` 64 to a block of 128 lanes (``row_sum_lanes(2q + 1)``
      says where).

    zp: (3q + 2, P) the forward's pair limbs, then G's hi and lo rows;
    rows: (n, 7q + 2) the forward's columns, then ``c inv`` hi x q and
    lo x q; f32.  Returns ``(hi, lo, packed)``: hi and lo
    (8 (q + 1), P), packed (n, 128 ceil((2q + 1) / 64) P / block_p)."""
    n, k = rows.shape
    q2, p = zp.shape
    q = (q2 - 2) // 3
    assert k == 7 * q + 2 and n % block_n == 0 and block_n % PSI2_ROWS == 0
    assert p % block_p == 0 and block_p % LANES == 0
    acc = jax.ShapeDtypeStruct((PSI2_ROWS * (q + 1), p), jnp.float32)
    acc_spec = block_spec((PSI2_ROWS * (q + 1), block_p), lambda j, i: (0, j))
    width = LANES * -(-(2 * q + 1) // 64)
    return pl.pallas_call(
        functools.partial(_psi2_bwd_kernel, interpret=interpret),
        grid=(p // block_p, n // block_n),
        in_specs=[
            block_spec(INV_FACT_DD.shape, lambda j, i: (0, 0)),
            block_spec((q2, block_p), lambda j, i: (0, j)),
            block_spec((block_n, k), lambda j, i: (i, 0)),
        ],
        out_specs=[acc_spec, acc_spec,
                   block_spec((block_n, width), lambda j, i: (i, j))],
        out_shape=[acc, acc, jax.ShapeDtypeStruct(
            (n, width * (p // block_p)), jnp.float32)],
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
