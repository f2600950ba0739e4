"""Pallas TPU kernel for the fused regression map step — the SGPR hot path.

The regression map (`stats.partial_stats`, ``s is None``) needs three
statistics of the kernel slab ``knm = k(X, Z)``:

    b = Sum_i w_i k_ii          ()        (psi0 sum; sf2 * Sum w for SE)
    C = knm^T (w . Y)           (m, d)
    D = (knm . w)^T knm         (m, m)

A mechanical XLA lowering materialises the full (n, m) slab in HBM and
re-reads it for each contraction — three round trips of O(n m) bytes. This
kernel evaluates ``knm`` tile-by-tile in VMEM and folds all three statistics
in the same grid pass, so the slab never exists outside VMEM.

The ARD exponent uses the psi-stats refactoring trick one order lower than
psi2: with ``inv_q = 1/ell_q^2``,

    E[i, a] = -1/2 Sum_q (x_iq - z_aq)^2 inv_q
            = alpha_i + M_i. @ Zc.a,
    alpha_i = -1/2 Sum_q x_iq^2 inv_q
    M       = [x * inv, -inv/2]           (n, 2q)
    Zc      = [z; z^2] (per column a)     (2q, m)

so each tile is one MXU matmul + exp (``_common.se_tile``, which contracts
lane dims so no in-kernel transpose or concat is needed), and the
contractions are two more MXU matmuls over the row (sublane) axis.

Grid (a_tiles, b_tiles, n_tiles), n innermost so every output block's
reduction visits are consecutive (the revolving-accumulator contract):
  D block (a, b) accumulates over n (in f32: exactly, in int32 level sums
    over row groups, emitted as an f32 (hi, lo) pair; see LIMB_BITS);
  C block (a, 0) accumulates only on the b == 0 sweep;
  b_stat (1, 1)  accumulates only on the a == b == 0 sweep.

Tiling contract (enforced/padded by ops.py):
  n % block_n == 0, m % block_m == 0, q and d padded to multiples of 8.
  On the TPU block_m is a multiple of 128: the (block_m, block_m) D block
  is lane-aligned only then (docs/kernels.md).
  Padding is NEUTRAL: padded latent dims carry x=z=0, inv_ell2=1 (zero
  exponent contribution); padded data rows carry w=0 (zero weight kills all
  three statistics); padded y columns are 0; padded inducing rows are
  sliced off the outputs.

The backward (``reg_stats_bwd_pallas``) recomputes the tiles the same way
and contracts them with the cotangents, so its slab stays in VMEM too:
with G = dD + dD^T, dK = w (K G) + (w y) dC^T and dE = dK . K, every
gradient is a small contraction of dE (its docstring). Grid (n_tiles,
a_tiles, b_tiles), rows outermost: the row cotangents' blocks are visited
consecutively, and the (m, q) partials are single blocks resident over
the whole grid. (K G)[:, a] sums over b in a VMEM scratch. Padded inducing
rows have zero G rows and columns and zero dC, padded data rows w=0: both
give dE = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._common import block_spec, dot_nn, dot_nt, dot_tn, se_tile


# On the f32 path D is summed exactly: sqrt(w) K, scaled into [0, 1], is
# rounded to a GRID_BITS-bit fixed-point grid and split into LIMBS int8
# limbs of LIMB_BITS bits, whose products the MXU sums in int32 without
# rounding. D is then the Gram matrix of the rounded rows to ~2^-48, so
# L^-1 D L^-T stays positive semi-definite however ill-conditioned Kmm
# is; an f32 sum's rounding is not a Gram matrix, and Kmm's least
# eigenvalue (the jitter) multiplies it ~1e6 once the lengthscales grow.
LIMB_BITS, LIMBS = 7, 3
GRID_BITS = LIMB_BITS * LIMBS
LEVELS = 2 * LIMBS - 1         # limb pairs grouped by the sum of their ranks


def _rows_per_group(block_n):
    """Row tiles whose level sums fit int32: a level adds at most LIMBS
    products of two limbs < 2^LIMB_BITS for each row."""
    per_tile = LIMBS * ((1 << LIMB_BITS) - 1) ** 2 * block_n
    return max(1, (2 ** 31 - 1) // per_tile)


def _limbs(u):
    """u in [0, 1] on the grid, as int8 limbs, the most significant first."""
    q = (jnp.clip(u, 0.0, 1.0) * ((1 << GRID_BITS) - 1) + 0.5).astype(
        jnp.int32)
    mask = (1 << LIMB_BITS) - 1
    return [((q >> (LIMB_BITS * (LIMBS - 1 - p))) & mask).astype(jnp.int8)
            for p in range(LIMBS)]


def _dot_tn_i32(a, b):
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _levels_to_f32_pair(acc):
    """``sum_l 2^(LIMB_BITS (LEVELS - 1 - l)) acc[l]`` of non-negative int32
    level sums as an unevaluated f32 pair (hi, lo), to ~2^-48 relative:
    each level is split into 15- and 16-bit halves, exact in f32, and the
    halves are added with Knuth's two-sum, which keeps every rounding
    error in ``lo``."""
    hi = lo = None
    for lvl in range(LEVELS):
        s = acc[lvl]
        scale = float(2 ** (LIMB_BITS * (LEVELS - 1 - lvl)))
        for part in ((s >> 16).astype(jnp.float32) * (scale * 65536.0),
                     (s & 0xFFFF).astype(jnp.float32) * scale):
            if hi is None:
                hi, lo = part, jnp.zeros_like(part)
                continue
            t = hi + part
            bb = t - hi
            lo = lo + ((hi - (t - bb)) + (part - bb))
            hi = t
    return hi, lo


def _reg_stats_kernel(inv_ref, sf2_ref, za_ref, zb_ref, x_ref, y_ref, w_ref,
                      sw_ref, b_ref, c_ref, d_ref, acc_ref, *,
                      tiles_per_group):
    a_i = pl.program_id(0)
    b_i = pl.program_id(1)
    k = pl.program_id(2)
    first_b = b_i == 0
    first_ab = jnp.logical_and(a_i == 0, first_b)
    in_group = lax.rem(k, jnp.int32(tiles_per_group))

    @pl.when(jnp.logical_and(first_ab, k == 0))
    def _init_b():
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(jnp.logical_and(first_b, k == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(in_group == 0)
    def _init_d():
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    inv = inv_ref[...]                                        # (1, q)
    sf2 = sf2_ref[...]                                        # (1, 1)
    x = x_ref[...]                                            # (bn, q)
    w = w_ref[...]                                            # (bn, 1)

    ka = se_tile(x, inv, sf2, za_ref[...])                    # (bn, bm)
    kb = se_tile(x, inv, sf2, zb_ref[...])

    if d_ref.dtype == jnp.float32:
        sw = sw_ref[...]                                      # sqrt(w) / scale
        la, lb = _limbs(ka * sw), _limbs(kb * sw)
        for lvl in range(LEVELS):
            for p in range(max(0, lvl - LIMBS + 1), min(lvl, LIMBS - 1) + 1):
                acc_ref[lvl] += _dot_tn_i32(la[p], lb[lvl - p])

        @pl.when(jnp.logical_or(in_group == tiles_per_group - 1,
                                k == pl.num_programs(2) - 1))
        def _emit_d():
            d_ref[0, 0], d_ref[0, 1] = _levels_to_f32_pair(acc_ref)
    else:
        d_ref[0, 0] += dot_tn(ka * w, kb)

    @pl.when(first_b)
    def _acc_c():
        c_ref[...] += dot_tn(ka, w * y_ref[...])

    @pl.when(first_ab)
    def _acc_b():
        # A (1, 1) vector store: Mosaic refuses scalar stores to VMEM.
        b_ref[...] += sf2 * jnp.sum(w, axis=0, keepdims=True)


def reg_stats_pallas(inv_ell2, sf2, z, x, y, w, sw, *, block_n=128,
                     block_m=128, interpret=False):
    """Fused (b, C, D) regression statistics. All inputs pre-padded (ops.py).

    inv_ell2: (1, q); sf2: (1, 1); z: (m, q); x: (n, q); y: (n, d); w and
    ``sw = sqrt(w) / scale``: (n, 1), with ``sqrt(w) K / scale <= 1``.
    Returns b (1, 1), C (m, d) and D's parts (groups, 2, m, m) in the
    input dtype; D is the sum of the parts. In float32 the parts are each
    row group's (hi, lo) pair of the grid's exact sum, and D their sum
    times ``(scale / (2^GRID_BITS - 1))^2`` (``ops.py``); in any other
    dtype the parts are D and 0 in one group.
    """
    n, q = x.shape
    m = z.shape[0]
    d = y.shape[1]
    assert n % block_n == 0 and m % block_m == 0
    dt = x.dtype
    n_tiles = n // block_n
    tpg = _rows_per_group(block_n) if dt == jnp.float32 else n_tiles
    groups = -(-n_tiles // tpg)
    grid = (m // block_m, m // block_m, n_tiles)
    return pl.pallas_call(
        functools.partial(_reg_stats_kernel, tiles_per_group=tpg),
        grid=grid,
        in_specs=[
            block_spec((1, q), lambda a, b, k: (0, 0)),              # inv_ell2
            block_spec((1, 1), lambda a, b, k: (0, 0)),              # sf2
            block_spec((block_m, q), lambda a, b, k: (a, 0)),        # z_a
            block_spec((block_m, q), lambda a, b, k: (b, 0)),        # z_b
            block_spec((block_n, q), lambda a, b, k: (k, 0)),        # x
            block_spec((block_n, d), lambda a, b, k: (k, 0)),        # y
            block_spec((block_n, 1), lambda a, b, k: (k, 0)),        # w
            block_spec((block_n, 1), lambda a, b, k: (k, 0)),        # sw
        ],
        out_specs=[
            block_spec((1, 1), lambda a, b, k: (0, 0)),              # b
            block_spec((block_m, d), lambda a, b, k: (a, 0)),        # C
            block_spec((1, 2, block_m, block_m),                     # D parts
                       lambda a, b, k: (lax.div(k, jnp.int32(tpg)), 0, a, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), dt),
            jax.ShapeDtypeStruct((m, d), dt),
            jax.ShapeDtypeStruct((groups, 2, m, m), dt),
        ],
        scratch_shapes=[pltpu.VMEM((LEVELS, block_m, block_m), jnp.int32)],
        interpret=interpret,
    )(inv_ell2, sf2, z, z, x, y, w, sw)


def _reg_stats_bwd_kernel(inv_ref, sf2_ref, za_ref, zb_ref, x_ref, y_ref,
                          w_ref, g_ref, dc_ref,
                          p_ref, s_ref, u_ref, dx_ref, dy_ref, dw_ref,
                          kg_ref, *, one_tile):
    k = pl.program_id(0)
    a_i = pl.program_id(1)
    b_i = pl.program_id(2)
    first_ab = jnp.logical_and(a_i == 0, b_i == 0)

    @pl.when(jnp.logical_and(first_ab, k == 0))
    def _init_hyp():
        p_ref[...] = jnp.zeros_like(p_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(first_ab)
    def _init_rows():
        dx_ref[...] = jnp.zeros_like(dx_ref)
        dy_ref[...] = jnp.zeros_like(dy_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(b_i == 0)
    def _init_kg():
        kg_ref[...] = jnp.zeros_like(kg_ref)

    inv = inv_ref[...]                                        # (1, q)
    sf2 = sf2_ref[...]                                        # (1, 1)
    x = x_ref[...]                                            # (bn, q)
    kb = se_tile(x, inv, sf2, zb_ref[...])                    # (bn, bm)
    kg_ref[...] += dot_nn(kb, g_ref[...])

    @pl.when(b_i == pl.num_programs(2) - 1)
    def _contract():
        w = w_ref[...]                                        # (bn, 1)
        y = y_ref[...]                                        # (bn, d)
        dc = dc_ref[...]                                      # (bm, d)
        za = za_ref[...]                                      # (bm, q)
        ka = kb if one_tile else se_tile(x, inv, sf2, za)     # (bn, bm)
        kg = kg_ref[...]                                      # (K G)[:, a]
        kdc = dot_nn(ka, dc)                                  # (bn, d)
        de = (w * kg + dot_nt(w * y, dc)) * ka                # dK * K
        r = jnp.sum(de, axis=1, keepdims=True)                # (bn, 1)
        bm = za.shape[0]
        rows = pl.ds(pl.multiple_of(a_i * bm, bm), bm)
        p_ref[rows, :] += dot_tn(de, x)
        s_ref[rows, :] += dot_tn(de, jnp.ones((de.shape[0], 8), de.dtype))
        u_ref[...] += jnp.sum(r * x * x, axis=0, keepdims=True)
        dx_ref[...] += inv * (dot_nn(de, za) - r * x)
        dy_ref[...] += w * kdc
        dw_ref[...] += (0.5 * jnp.sum(ka * kg, axis=1, keepdims=True)
                        + jnp.sum(y * kdc, axis=1, keepdims=True))


def reg_stats_bwd_pallas(inv_ell2, sf2, z, x, y, w, g, dc, *, block_n=128,
                         block_m=128, interpret=False):
    """Partials of the (b, C, D) backward. Inputs padded as the forward's,
    plus the cotangents ``g = dD + dD^T`` (m, m) and ``dc`` (m, d).

    With ``dE = (w (K g) + (w y) dc^T) . K`` it returns ``P = dE^T x``
    (m, q), ``s`` (m, 8), every column ``sum_i dE[i, a]``, ``u`` (1, q)
    ``= sum_i r_i x_i^2`` with ``r_i = sum_a dE[i, a]``, and the row
    cotangents ``dx = inv (dE z - r x)`` (n, q), ``dy = w (K dc)`` (n, d)
    and ``dw`` (n, 1) without the ``b`` term, all in the input dtype.
    """
    n, q = x.shape
    m = z.shape[0]
    d = y.shape[1]
    assert n % block_n == 0 and m % block_m == 0
    dt = x.dtype
    grid = (n // block_n, m // block_m, m // block_m)
    return pl.pallas_call(
        functools.partial(_reg_stats_bwd_kernel, one_tile=m == block_m),
        grid=grid,
        in_specs=[
            block_spec((1, q), lambda k, a, b: (0, 0)),              # inv_ell2
            block_spec((1, 1), lambda k, a, b: (0, 0)),              # sf2
            block_spec((block_m, q), lambda k, a, b: (a, 0)),        # z_a
            block_spec((block_m, q), lambda k, a, b: (b, 0)),        # z_b
            block_spec((block_n, q), lambda k, a, b: (k, 0)),        # x
            block_spec((block_n, d), lambda k, a, b: (k, 0)),        # y
            block_spec((block_n, 1), lambda k, a, b: (k, 0)),        # w
            block_spec((block_m, block_m), lambda k, a, b: (b, a)),  # g[b, a]
            block_spec((block_m, d), lambda k, a, b: (a, 0)),        # dc_a
        ],
        out_specs=[
            block_spec((m, q), lambda k, a, b: (0, 0)),              # P
            block_spec((m, 8), lambda k, a, b: (0, 0)),              # s
            block_spec((1, q), lambda k, a, b: (0, 0)),              # u
            block_spec((block_n, q), lambda k, a, b: (k, 0)),        # dx
            block_spec((block_n, d), lambda k, a, b: (k, 0)),        # dy
            block_spec((block_n, 1), lambda k, a, b: (k, 0)),        # dw
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, q), dt),
            jax.ShapeDtypeStruct((m, 8), dt),
            jax.ShapeDtypeStruct((1, q), dt),
            jax.ShapeDtypeStruct((n, q), dt),
            jax.ShapeDtypeStruct((n, d), dt),
            jax.ShapeDtypeStruct((n, 1), dt),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, block_m), dt)],
        interpret=interpret,
    )(inv_ell2, sf2, z, z, x, y, w, g, dc)
