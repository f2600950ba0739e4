"""jit'd public wrapper around the fused regression-stats Pallas kernel.

Handles padding to tile boundaries (all pads are NEUTRAL — padded latent
dims carry x=z=0, inv_ell2=1; padded data rows carry w=0; padded y columns
are 0; padded inducing rows are sliced off the outputs), backend selection
(interpret=True off-TPU), and the hyper-parameter plumbing from the core
library's log-space dict.

Precision contract: on TPU the kernel computes in f32 (MXU-native); under
interpret mode it keeps the caller's dtype, so the CI parity tests run the
exact f64 math of the XLA path.

Differentiation: ``pallas_call`` has no VJP on this JAX version, so the op
carries a ``custom_vjp`` — forward is the fused kernel, backward recomputes
the (block, m) slab with the same XLA ops as the monolithic path
(``stats.partial_stats``'s ``s is None`` branch). Under the chunked map the
op sees block-sized operands, so the backward's slab stays O(block * m).
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


def _fwd_impl(block_n, block_m, interpret, hyp, z, x, y, w):
    m, d = z.shape[0], y.shape[1]
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

    b, c, d_stat = _k.reg_stats_pallas(inv_p, sf2, z_p, x_p, y_p, w_p,
                                       block_n=block_n, block_m=block_m,
                                       interpret=interpret)
    # Back to the caller's dtype: the chunked map folds blocks in the
    # dtype a block returns, and an f32 fold over ~1000 blocks costs ~1e-6
    # relative (and makes the sum depend on how rows are split over shards).
    out = x.dtype
    return (b[0, 0].astype(out), c[:m, :d].astype(out),
            d_stat[:m, :m].astype(out))


def _vjp_fwd(block_n, block_m, interpret, hyp, z, x, y, w):
    out = _fwd_impl(block_n, block_m, interpret, hyp, z, x, y, w)
    return out, (hyp, z, x, y, w)


def _vjp_bwd(block_n, block_m, interpret, res, cts):
    del block_n, block_m, interpret
    out, vjp = jax.vjp(reg_stats_dense, *res)
    # Forward may have run in f32 (TPU); match the reference dtypes.
    cts = tuple(jnp.asarray(c, o.dtype) for c, o in zip(cts, out))
    return vjp(cts)


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
