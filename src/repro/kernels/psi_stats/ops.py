"""jit'd public wrappers around the psi-statistics Pallas kernels.

Handles padding to tile boundaries (all pads are NEUTRAL — padded data
rows carry w=0 (psi2: c=0); padded inducing rows and psi2's padded pairs
are sliced off the output; psi1's padded latent dims carry mu=s=z=0,
ell2=1), the operand layouts the kernels want (psi2's limbs and hi/lo
pairs, computed in f64), backend selection (interpret=True off-TPU), and
the hyper-parameter plumbing from the core library's log-space dict.

``psi2`` carries a ``custom_vjp`` (``pallas_call`` has no VJP on this JAX
version), so the kernel can sit inside ``jax.grad`` of the bound (the
engine's ``kernel_backend="pallas"`` path): forward is the Pallas kernel,
backward a second kernel (``psi2_bwd``) that recomputes every term in
the forward's compensated f32 and sums what the cotangents need; the
residuals are the inputs alone.  ``gp_kernels.psi2_mxu`` (f64 XLA) is
the backward's test oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import gp_kernels as gpk
from .._common import on_tpu as _on_tpu
from .._common import pad_to as _pad_to
from . import kernel as _k


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _psi2(block_n, block_p, interpret, hyp, z, mu, s, w):
    return _psi2_fwd_impl(block_n, block_p, interpret, hyp, z, mu, s, w)


def _limbs(v, grid1, grid2):
    """``v = v1 + v2 + v3`` in f64: v1 on ``grid1``, v2 on ``grid2`` with
    ``|v2| <= grid1 / 2``, and the rest; exact."""
    v1 = jnp.round(v / grid1) * grid1
    r = v - v1
    v2 = jnp.round(r / grid2) * grid2
    return v1, v2, r - v2


def _pair(v):
    """An f64 value as an f32 pair ``hi + lo``."""
    hi = v.astype(jnp.float32)
    return hi, (v - hi).astype(jnp.float32)


def _operands(mu, zbar, inv, c):
    """The forward kernel's operands from f64 values: the rows (n, 5q + 2)
    ``[mu1, mu2, mu3, inv_hi, inv_lo] x q, c_hi, c_lo`` and the pair limbs
    (3q, P) ``[zb1, zb2, zb3] x q``, f32 (kernel.py)."""
    f32 = jnp.float32
    # One grid for mu and zbar, so their limbs' differences are exact:
    # |v| <= 2^e, v1 on 2^(e-11), v2 on 2^(e-22) (12 bits each; their
    # differences' sum has at most 24).
    top = jnp.maximum(jnp.max(jnp.abs(mu)), jnp.max(jnp.abs(zbar)))
    e = jnp.floor(jnp.log2(jnp.maximum(top, 1e-30))) + 1.0
    grid1, grid2 = jnp.exp2(e - 11.0), jnp.exp2(e - 22.0)
    mu_l, zb_l = _limbs(mu, grid1, grid2), _limbs(zbar, grid1, grid2)
    cols = [*mu_l, *_pair(inv), *(t[:, None] for t in _pair(c))]
    rows = jnp.concatenate([t.astype(f32) for t in cols], axis=1)
    zp = jnp.concatenate([t.astype(f32) for t in zb_l], axis=1).T  # (3q, P)
    return rows, zp


def _psi2_fwd_impl(block_n, block_p, interpret, hyp, z, mu, s, w):
    # Everything outside the kernel runs in f64: the kernel's operands are
    # f64 values cut into f32 limbs or hi/lo pairs (kernel.py).
    f64 = jnp.float64
    out_dtype = jnp.result_type(z, mu)
    c, inv, zbar, _ = _inputs(hyp, z, s, w)
    z = jnp.asarray(z, f64)
    m = z.shape[0]
    rows, zp = _operands(jnp.asarray(mu, f64), zbar, inv, c)
    hi, lo = _k.psi2_pallas(_pad_to(zp, block_p, 1),
                            _pad_to(rows, block_n, 0),
                            block_n=block_n, block_p=block_p,
                            interpret=interpret)
    sums = (jnp.sum(hi.astype(f64), axis=0)
            + jnp.sum(lo.astype(f64), axis=0))[:zbar.shape[0]]
    d_stat = gpk.from_pairs(sums, m)
    # The i-free factor of every term: sf4 * exp(-1/4 sum_q dz^2 / ell2).
    ell2 = jnp.exp(2.0 * jnp.asarray(hyp["log_ell"], f64))
    dz = z[:, None, :] - z[None, :, :]
    static = -0.25 * jnp.sum(dz * dz / ell2, axis=-1)               # (m, m)
    sf4 = jnp.exp(2.0 * jnp.asarray(hyp["log_sf2"], f64))
    return (sf4 * jnp.exp(static) * d_stat).astype(out_dtype)


def _psi2_vjp_fwd(block_n, block_p, interpret, hyp, z, mu, s, w):
    out = _psi2_fwd_impl(block_n, block_p, interpret, hyp, z, mu, s, w)
    return out, (hyp, z, mu, s, w)


def _psi2_vjp_bwd(block_n, block_p, interpret, res, ct):
    return psi2_bwd(*res, ct, block_n=block_n, block_p=block_p,
                    interpret=interpret)


def _inputs(hyp, z, s, w):
    """The f64 values the kernels read, as functions of the inputs that
    the backward differentiates: c (n,), inv (n, q), zbar (P, q) and each
    pair's log factor ``log(sf4) + static`` (P,), P = m (m + 1) / 2."""
    f64 = jnp.float64
    z, s, w = (jnp.asarray(a, f64) for a in (z, s, w))
    ell2 = jnp.exp(2.0 * jnp.asarray(hyp["log_ell"], f64))
    den = ell2 + 2.0 * s
    inv = 1.0 / den
    c = w * jnp.exp(-0.5 * jnp.sum(jnp.log(den / ell2), axis=1))
    a, b = np.triu_indices(z.shape[0])
    za, zb = z[a], z[b]
    static = -0.25 * jnp.sum((za - zb) ** 2 / ell2, axis=1)
    return c, inv, 0.5 * (za + zb), \
        2.0 * jnp.asarray(hyp["log_sf2"], f64) + static


# Its own jit, so the profiler names the kernel's instruction psi2_bwd.N.
@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_p", "interpret"))
def psi2_bwd(hyp, z, mu, s, w, ct, *, block_n, block_p, interpret):
    """Every cotangent of ``psi2`` from the backward kernel's sums
    (``kernel.psi2_bwd_pallas``), assembled in f64 with O(n q + P q) ops.

    Per row i and pair p, with ``e = exp(-E_ip)``, ``d = mu_i - zbar_p``
    and ``G_p = ct_p sf4 exp(static_p)``: ``cbar_i = sum_p G e``,
    ``mubar = -2 c inv sum_p G e d``, ``invbar = -c sum_p G e d^2``,
    ``zbarbar_p = 2 G_p sum_i c inv e d`` and the pair's log factor's
    ``G_p S_p``; ``jax.vjp`` of ``_inputs`` takes them to the inputs.
    The tiles are the forward's, ``block_p`` rounded up to whole lane
    tiles."""
    f64 = jnp.float64
    (n, q), m = mu.shape, z.shape[0]
    a, b = np.triu_indices(m)
    (c, inv, zbar, log_f), pull = jax.vjp(_inputs, hyp, z, s, w)
    rows, zp = _operands(jnp.asarray(mu, f64), zbar, inv, c)
    ct = jnp.asarray(ct, f64)
    # Each pair's cotangent: its two entries', the diagonal's once.
    ct_p = (ct + ct.T)[a, b] * np.where(a == b, 0.5, 1.0)
    g = ct_p * jnp.exp(log_f)
    block_p = -(-block_p // _k.LANES) * _k.LANES
    rows = jnp.concatenate([rows, *_pair(c[:, None] * inv)], axis=1)
    zp = jnp.concatenate([zp, *(t[None] for t in _pair(g))], axis=0)
    hi, lo, packed = _k.psi2_bwd_pallas(
        _pad_to(zp, block_p, 1), _pad_to(rows, block_n, 0),
        block_n=block_n, block_p=block_p, interpret=interpret)
    by_pair = (hi.astype(f64) + lo.astype(f64)).reshape(
        q + 1, _k.PSI2_ROWS, -1).sum(axis=1)[:, :a.size]
    lanes = _k.row_sum_lanes(2 * q + 1)
    by_row = packed[:n].reshape(n, hi.shape[1] // block_p, -1).astype(f64)
    by_row = (by_row[:, :, lanes] + by_row[:, :, lanes + 1]).sum(axis=1)
    ge, ged, ged2 = by_row[:, 0], by_row[:, 1:q + 1], by_row[:, q + 1:]
    dhyp, dz, ds, dw = pull((ge, -c[:, None] * ged2,
                             2.0 * g[:, None] * by_pair[1:].T,
                             g * by_pair[0]))
    dmu = -2.0 * (c[:, None] * inv) * ged
    return dhyp, dz, dmu.astype(mu.dtype), ds, dw


_psi2.defvjp(_psi2_vjp_fwd, _psi2_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("block_n", "block_p", "interpret"))
def psi2(hyp: dict, z, mu, s, w, block_n: int = 256, block_p: int = 256,
         interpret: bool | None = None):
    """Weighted Psi2 = sum_i w_i <K_mi K_im> via the Pallas kernel, (m, m),
    to about 1e-13 relative per entry.  ``block_n`` rows and ``block_p``
    inducing pairs (a <= b) per grid step."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _psi2(block_n, block_p, interpret, hyp, z, mu, s, w)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def psi1(hyp: dict, z, mu, s, block_n: int = 256, block_m: int = 128,
         interpret: bool | None = None):
    """Psi1 = <K_nm> via the Pallas kernel. (n, m)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    n, m = mu.shape[0], z.shape[0]
    f32 = jnp.float32
    ell2 = jnp.exp(2.0 * hyp["log_ell"]).astype(f32)[None, :]
    sf2 = jnp.exp(hyp["log_sf2"]).astype(f32)[None, None]

    q_pad = 8
    ell2 = _pad_to(ell2, q_pad, 1, value=1.0)
    z_p = _pad_to(_pad_to(z.astype(f32), q_pad, 1), block_m, 0)
    mu_p = _pad_to(_pad_to(mu.astype(f32), q_pad, 1), block_n, 0)
    s_p = _pad_to(_pad_to(s.astype(f32), q_pad, 1), block_n, 0)

    out = _k.psi1_pallas(ell2, sf2, z_p, mu_p, s_p,
                         block_n=block_n, block_m=block_m, interpret=interpret)
    return out[:n, :m]


def psi2_fn_for_engine(block_n: int = 256, block_p: int = 256, kernel=None):
    """Adapter matching core.stats.partial_stats(psi2_fn=...) signature.

    Dispatch shim for the compositional kernel layer: the fused Pallas
    kernel computes the SE-ARD closed form, so the full-width SE-ARD
    expression (the default) gets the fast path; any other expression runs
    its own ``Kernel.psi2`` (analytic or quadrature) through XLA — same
    signature, parity covered by tests/test_kernel_zoo.py.
    """
    from ...core.covariance import as_kernel, is_fused_se

    kernel = as_kernel(kernel)
    if is_fused_se(kernel):
        def fn(hyp, z, mu, s, w):
            return psi2(hyp, z, mu, s, w, block_n=block_n, block_p=block_p)
    else:
        def fn(hyp, z, mu, s, w):
            return kernel.psi2(hyp, z, mu, s, w)

    return fn
