"""jit'd public wrapper around the fused regression-stats Pallas kernel.

Handles padding to tile boundaries (all pads are NEUTRAL — padded latent
dims carry x=z=0, inv_ell2=1; padded data rows carry w=0; padded y columns
are 0; padded inducing rows are sliced off the outputs), backend selection
(interpret=True off-TPU), and the hyper-parameter plumbing from the core
library's log-space dict.

Precision contract: on TPU the kernel computes its tiles in f32 at full
MXU precision, sums C in f32 and D exactly, as the Gram matrix of the
tiles rounded to a 21-bit grid (``kernel.LIMB_BITS``), so D cannot lose
positive definiteness against an ill-conditioned Kmm; under interpret
mode it keeps the caller's dtype, so the CI parity tests run the exact
f64 math of the XLA path.

Differentiation: ``pallas_call`` has no VJP on this JAX version, so the op
carries a ``custom_vjp``. Forward is the fused kernel; backward is a second
fused kernel (``kernel.reg_stats_bwd_pallas``) that recomputes each K tile
in VMEM and contracts it with the cotangents, so the (block, m) slab never
reaches HBM. It hands back (m, q) partials and the per-row cotangents,
from which ``_vjp_bwd`` assembles the gradients in the caller's dtype (f64
here) with O(m q) ops. Its tiles are f32 at full MXU precision on the
TPU, the caller's dtype under interpret. ``core.stats.reg_stats_dense``
is the reference both kernels are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.stats import reg_stats_dense
from .._common import on_tpu as _on_tpu
from .._common import pad_to as _pad_to
from . import kernel as _k


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _reg_stats(block_n, block_m, interpret, hyp, z, x, y, w):
    return _fwd_impl(block_n, block_m, interpret, hyp, z, x, y, w)


def _operands(block_n, block_m, interpret, hyp, z, x, y, w):
    """Both kernels' padded operands ``(inv_ell2, sf2, z, x, y, w)``."""
    # f32 on the MXU; caller dtype (f64 in this repo) under interpret.
    dt = x.dtype if interpret else jnp.float32
    inv_ell2 = jnp.exp(-2.0 * hyp["log_ell"]).astype(dt)[None, :]   # (1, q)
    sf2 = jnp.exp(hyp["log_sf2"]).astype(dt)[None, None]            # (1, 1)

    pad8 = 8
    inv_p = _pad_to(inv_ell2, pad8, 1, value=1.0)
    z_p = _pad_to(_pad_to(z.astype(dt), pad8, 1), block_m, 0)
    x_p = _pad_to(_pad_to(x.astype(dt), pad8, 1), block_n, 0)
    y_p = _pad_to(_pad_to(y.astype(dt), pad8, 1), block_n, 0)
    w_p = _pad_to(w.astype(dt)[:, None], block_n, 0)
    return inv_p, sf2, z_p, x_p, y_p, w_p


def _fwd_impl(block_n, block_m, interpret, hyp, z, x, y, w):
    m, d = z.shape[0], y.shape[1]
    ops = _operands(block_n, block_m, interpret, hyp, z, x, y, w)
    # Out in the caller's dtype: the chunked map folds blocks in the dtype
    # a block returns, and an f32 fold over ~1000 blocks costs ~1e-6
    # relative (and makes the sum depend on how rows are split over shards).
    out = x.dtype
    # sqrt(w) K / scale <= 1 for the f32 path's fixed-point grid.
    dt = ops[0].dtype
    inv_scale = (jnp.exp(-hyp["log_sf2"]) / jnp.sqrt(
        jnp.maximum(jnp.max(w), jnp.finfo(dt).tiny))).astype(dt)
    b, c, parts = _k.reg_stats_pallas(*ops, jnp.sqrt(ops[5]) * inv_scale,
                                      block_n=block_n, block_m=block_m,
                                      interpret=interpret)
    d_stat = jnp.sum(parts.astype(out), axis=(0, 1))[:m, :m]
    if dt == jnp.float32:
        unit = 1.0 / (inv_scale.astype(out) * ((1 << _k.GRID_BITS) - 1))
        d_stat = unit * unit * d_stat
    return b[0, 0].astype(out), c[:m, :d].astype(out), d_stat


def _vjp_fwd(block_n, block_m, interpret, hyp, z, x, y, w):
    out = _fwd_impl(block_n, block_m, interpret, hyp, z, x, y, w)
    return out, (hyp, z, x, y, w)


def _vjp_bwd(block_n, block_m, interpret, res, cts):
    return reg_stats_bwd(*res, cts, block_n=block_n, block_m=block_m,
                         interpret=interpret)


# Its own jit, so the profiler names the kernel's instruction reg_stats_bwd.N.
@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def reg_stats_bwd(hyp, z, x, y, w, cts, *, block_n, block_m, interpret):
    """Every cotangent of ``reg_stats`` in the caller's dtype: the backward
    kernel's partials, assembled with O(m q) ops (notation of
    ``kernel.reg_stats_bwd_pallas``)."""
    db, dc, dd = cts
    (n, q), m, d = x.shape, z.shape[0], y.shape[1]
    ops = _operands(block_n, block_m, interpret, hyp, z, x, y, w)
    dt, out = ops[0].dtype, x.dtype
    g = _pad_to(_pad_to((dd + dd.T).astype(dt), block_m, 0), block_m, 1)
    dc_p = _pad_to(_pad_to(dc.astype(dt), 8, 1), block_m, 0)
    p, s, u, dx, dy, dw = _k.reg_stats_bwd_pallas(
        *ops, g, dc_p, block_n=block_n, block_m=block_m,
        interpret=interpret)

    inv = jnp.exp(-2.0 * hyp["log_ell"])
    sf2 = jnp.exp(hyp["log_sf2"])
    zk = ops[2][:m, :q].astype(out)              # z as the kernel saw it
    p, s, u = p[:m, :q].astype(out), s[:m, 0].astype(out), u[0, :q].astype(out)
    # dz_a = inv sum_i dE[i, a] (x_i - z_a), dlog_ell = inv sum dE (x - z)^2.
    dz = inv * (p - s[:, None] * zk)
    dlog_ell = inv * (u - 2.0 * jnp.sum(p * zk, axis=0)
                      + jnp.sum(s[:, None] * zk * zk, axis=0))
    b_sf2 = db * sf2                             # d b / d w_i
    dhyp = jax.tree.map(jnp.zeros_like, hyp)
    dhyp["log_ell"] = dlog_ell.astype(hyp["log_ell"].dtype)
    dhyp["log_sf2"] = (jnp.sum(s) + b_sf2 * jnp.sum(w)).astype(
        hyp["log_sf2"].dtype)
    return (dhyp, dz.astype(z.dtype),
            dx[:n, :q].astype(x.dtype), dy[:n, :d].astype(y.dtype),
            (dw[:n, 0].astype(out) + b_sf2).astype(w.dtype))


_reg_stats.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def reg_stats(hyp: dict, z, x, y, w, block_n: int = 128, block_m: int = 128,
              interpret: bool | None = None):
    """Fused regression map statistics via the Pallas kernel.

    Returns ``(b, C, D)``: the psi0 sum (), ``knm^T (w . Y)`` (m, d) and
    ``(knm . w)^T knm`` (m, m) — without materialising ``knm`` in HBM.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _reg_stats(block_n, block_m, interpret, hyp, z, x, y, w)


def reg_stats_fn_for_engine(block_n: int = 128, block_m: int = 128,
                            kernel=None):
    """Adapter matching core.stats.partial_stats(reg_stats_fn=...) signature.

    Dispatch shim for the compositional kernel layer: the fused Pallas
    kernel is specialised to the full-width SE-ARD covariance, so that
    expression (the default) gets the fast path; any other expression gets
    a generic XLA fallback with identical signature and semantics (parity
    asserted in tests/test_kernel_zoo.py).
    """
    from ...core.covariance import as_kernel, is_fused_se

    kernel = as_kernel(kernel)
    if is_fused_se(kernel):
        def fn(hyp, z, x, y, w):
            return reg_stats(hyp, z, x, y, w, block_n=block_n,
                             block_m=block_m)
    else:
        def fn(hyp, z, x, y, w):
            return reg_stats_dense(hyp, z, x, y, w, kernel=kernel)

    return fn
