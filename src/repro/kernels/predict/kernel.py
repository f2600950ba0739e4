"""Pallas TPU kernel for the fused serving predict step.

Serving needs two statistics of the query kernel slab ``ksm = k(X*, Z)``
against the frozen predictive state (``serve/posterior.py``):

    mean = ksm @ a_mean                      (t, d)
    quad = rowsum((ksm @ g) * ksm)           (t,)    var = k** - quad

A mechanical XLA lowering materialises the (t, m) slab in HBM and re-reads
it for each contraction.  This kernel evaluates ``ksm`` tile-by-tile in VMEM
and folds both statistics in the same grid pass — the serving twin of
``kernels/reg_stats`` (same ARD exponent refactoring: one MXU matmul + exp
per tile), but **forward-only**: prediction is never differentiated, so
there is no ``custom_vjp`` and no backward recompute.

Grid ``(t_tiles, a_tiles, b_tiles)`` — t outermost so each output block's
reduction visits are consecutive (the revolving-accumulator contract):
  quad block (t,) accumulates over every (a, b) cell:  (ka Gab) . kb ;
  mean block (t,) accumulates only on the b == 0 sweep: ka @ A_a.

Tiling contract (enforced/padded by ops.py):
  t % block_t == 0, m % block_m == 0, q and d padded to multiples of 8.
  On the TPU block_m is a multiple of 128: the (block_m, block_m) ``g``
  block is lane-aligned only then (docs/kernels.md).
  Padding is NEUTRAL: padded latent dims carry x=z=0, inv_ell2=1 (zero
  exponent contribution); padded inducing rows carry zero rows/cols of
  ``g`` and ``a_mean`` (their nonzero kernel columns multiply zeros);
  padded query rows compute garbage that ops.py slices off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._common import block_spec, dot_nn, se_tile


def _predict_kernel(inv_ref, sf2_ref, za_ref, zb_ref, x_ref, g_ref, a_ref,
                    mean_ref, quad_ref):
    a_i = pl.program_id(1)
    b_i = pl.program_id(2)
    first_b = b_i == 0
    first_ab = jnp.logical_and(a_i == 0, first_b)

    @pl.when(first_ab)
    def _init():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        quad_ref[...] = jnp.zeros_like(quad_ref)

    inv = inv_ref[...]                                        # (1, q)
    sf2 = sf2_ref[...]                                        # (1, 1)
    x = x_ref[...]                                            # (bt, q)

    ka = se_tile(x, inv, sf2, za_ref[...])                    # (bt, bm)
    kb = se_tile(x, inv, sf2, zb_ref[...])

    tmp = dot_nn(ka, g_ref[...])                              # (bt, bm)
    quad_ref[...] += jnp.sum(tmp * kb, axis=1, keepdims=True)

    @pl.when(first_b)
    def _acc_mean():
        mean_ref[...] += dot_nn(ka, a_ref[...])


def predict_pallas(inv_ell2, sf2, z, x, a_mean, g, *, block_t=128,
                   block_m=128, interpret=False):
    """Fused (mean, quad) serving statistics. All inputs pre-padded (ops.py).

    inv_ell2: (1, q); sf2: (1, 1); z: (m, q); x: (t, q); a_mean: (m, d);
    g: (m, m).  Returns (mean (t, d), quad (t, 1)) in the input dtype.
    """
    t, q = x.shape
    m = z.shape[0]
    d = a_mean.shape[1]
    assert t % block_t == 0 and m % block_m == 0
    dt = x.dtype
    grid = (t // block_t, m // block_m, m // block_m)
    return pl.pallas_call(
        _predict_kernel,
        grid=grid,
        in_specs=[
            block_spec((1, q), lambda i, a, b: (0, 0)),              # inv_ell2
            block_spec((1, 1), lambda i, a, b: (0, 0)),              # sf2
            block_spec((block_m, q), lambda i, a, b: (a, 0)),        # z_a
            block_spec((block_m, q), lambda i, a, b: (b, 0)),        # z_b
            block_spec((block_t, q), lambda i, a, b: (i, 0)),        # x
            block_spec((block_m, block_m), lambda i, a, b: (a, b)),  # g
            block_spec((block_m, d), lambda i, a, b: (a, 0)),        # a_mean
        ],
        out_specs=[
            block_spec((block_t, d), lambda i, a, b: (i, 0)),        # mean
            block_spec((block_t, 1), lambda i, a, b: (i, 0)),        # quad
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), dt),
            jax.ShapeDtypeStruct((t, 1), dt),
        ],
        interpret=interpret,
    )(inv_ell2, sf2, z, z, x, g, a_mean)
