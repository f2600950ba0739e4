"""Helpers shared by the kernel packages (the tiling contract's padding,
backend selection, and the Mosaic-safe building blocks — see
docs/kernels.md)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_to(x, mult, axis, value=0.0):
    """Pad ``axis`` up to a multiple of ``mult`` with ``value`` (neutral
    padding — the caller picks the value that contributes zero)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def block_spec(block_shape, index_map):
    """``pl.BlockSpec`` whose index map yields int32 block indices.

    ``import repro`` turns on x64, under which a literal ``0`` in an index
    map traces as int64; Mosaic cannot lower an index map that returns
    int64 (``failed to legalize operation 'func.return'``).  Casting here
    fixes every kernel's specs at one place, whatever the x64 flag, and
    leaves the kernels' own operand dtypes alone."""
    def i32_map(*grid_idx):
        return tuple(jnp.asarray(i, jnp.int32) for i in index_map(*grid_idx))

    return pl.BlockSpec(block_shape, i32_map)


_HI = lax.Precision.HIGHEST

_LOG2E = 1.4426950408889634
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4     # Cody-Waite split of ln 2


def exp_f32(x):
    """``exp`` of an f32 array to f32 round-off, from VPU multiply-adds.

    The TPU's own f32 ``exp`` (Mosaic and XLA alike) is off by up to
    5.6e-6 relative on a v5e (1.3e-6 on average), and the USPS GPLVM
    bound amplifies psi2 errors ~30x; this one stays under 1e-7.
    ``exp(x) = 2^k exp(r)``, ``|r| <= ln2 / 2``, ``exp(r)`` by its Taylor
    series to degree 7, ``2^k`` built in the exponent bits; results below
    the f32 normal range are 0 (the TPU flushes them anyway)."""
    k = jnp.floor(x * _LOG2E + 0.5)
    r = (x - k * _LN2_HI) - k * _LN2_LO
    p = 1.0 / 5040.0
    for c in (1.0 / 720, 1.0 / 120, 1.0 / 24, 1.0 / 6, 0.5, 1.0, 1.0):
        p = p * r + c
    ki = jnp.maximum(k, -126.0).astype(jnp.int32)
    two_k = lax.bitcast_convert_type(
        lax.shift_left(ki + 127, jnp.int32(23)), jnp.float32)
    return jnp.where(x < -87.0, 0.0, p * two_k)


def dot_nt(a, b):
    """``a @ b.T`` contracting both last (lane) dims — the MXU's native
    transposed-RHS form, so no in-kernel transpose or lane concat."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI)


def dot_tn(a, b):
    """``a.T @ b`` contracting both leading (sublane) dims."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI)


def dot_nn(a, b):
    """Plain ``a @ b`` at full f32 MXU precision."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI)


def se_tile(x, inv, sf2, z):
    """SE-ARD kernel tile ``k(x, z)`` (bn, bm) inside a Pallas body.

    ``x`` (bn, q), ``inv = 1/ell^2`` (1, q), ``sf2`` (1, 1), ``z`` (bm, q).
    The exponent ``-1/2 sum_q (x - z)^2 inv`` is expanded so the cross term
    is one MXU matmul: ``alpha_i + (x inv) z^T - 1/2 inv (z^2)^T``."""
    xi = x * inv
    alpha = -0.5 * jnp.sum(x * xi, axis=1, keepdims=True)           # (bn, 1)
    half_inv = jnp.broadcast_to(-0.5 * inv, x.shape)                 # (bn, q)
    e = alpha + dot_nt(xi, z) + dot_nt(half_inv, z * z)
    return sf2 * jnp.exp(e)
