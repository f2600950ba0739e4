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
  D block (a, b) accumulates over n;
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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._common import block_spec, dot_tn, se_tile


def _reg_stats_kernel(inv_ref, sf2_ref, za_ref, zb_ref, x_ref, y_ref, w_ref,
                      b_ref, c_ref, d_ref):
    a_i = pl.program_id(0)
    b_i = pl.program_id(1)
    k = pl.program_id(2)
    first_b = b_i == 0
    first_ab = jnp.logical_and(a_i == 0, first_b)

    @pl.when(jnp.logical_and(first_ab, k == 0))
    def _init_b():
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(jnp.logical_and(first_b, k == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(k == 0)
    def _init_d():
        d_ref[...] = jnp.zeros_like(d_ref)

    inv = inv_ref[...]                                        # (1, q)
    sf2 = sf2_ref[...]                                        # (1, 1)
    x = x_ref[...]                                            # (bn, q)
    w = w_ref[...]                                            # (bn, 1)

    ka = se_tile(x, inv, sf2, za_ref[...])                    # (bn, bm)
    kb = se_tile(x, inv, sf2, zb_ref[...])

    d_ref[...] += dot_tn(ka * w, kb)

    @pl.when(first_b)
    def _acc_c():
        c_ref[...] += dot_tn(ka, w * y_ref[...])

    @pl.when(first_ab)
    def _acc_b():
        # A (1, 1) vector store: Mosaic refuses scalar stores to VMEM.
        b_ref[...] += sf2 * jnp.sum(w, axis=0, keepdims=True)


def reg_stats_pallas(inv_ell2, sf2, z, x, y, w, *, block_n=128, block_m=128,
                     interpret=False):
    """Fused (b, C, D) regression statistics. All inputs pre-padded (ops.py).

    inv_ell2: (1, q); sf2: (1, 1); z: (m, q); x: (n, q); y: (n, d); w: (n, 1).
    Returns (b (1, 1), C (m, d), D (m, m)) in the input dtype.
    """
    n, q = x.shape
    m = z.shape[0]
    d = y.shape[1]
    assert n % block_n == 0 and m % block_m == 0
    dt = x.dtype
    grid = (m // block_m, m // block_m, n // block_n)
    return pl.pallas_call(
        _reg_stats_kernel,
        grid=grid,
        in_specs=[
            block_spec((1, q), lambda a, b, k: (0, 0)),              # inv_ell2
            block_spec((1, 1), lambda a, b, k: (0, 0)),              # sf2
            block_spec((block_m, q), lambda a, b, k: (a, 0)),        # z_a
            block_spec((block_m, q), lambda a, b, k: (b, 0)),        # z_b
            block_spec((block_n, q), lambda a, b, k: (k, 0)),        # x
            block_spec((block_n, d), lambda a, b, k: (k, 0)),        # y
            block_spec((block_n, 1), lambda a, b, k: (k, 0)),        # w
        ],
        out_specs=[
            block_spec((1, 1), lambda a, b, k: (0, 0)),              # b
            block_spec((block_m, d), lambda a, b, k: (a, 0)),        # C
            block_spec((block_m, block_m), lambda a, b, k: (a, b)),  # D
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), dt),
            jax.ShapeDtypeStruct((m, d), dt),
            jax.ShapeDtypeStruct((m, m), dt),
        ],
        interpret=interpret,
    )(inv_ell2, sf2, z, z, x, y, w)
