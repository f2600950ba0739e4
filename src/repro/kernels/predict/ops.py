"""jit'd public wrapper around the fused serving predict Pallas kernel.

Handles padding to tile boundaries (all pads are NEUTRAL — padded latent
dims carry x=z=0, inv_ell2=1; padded inducing rows carry zero ``g`` rows/
cols and zero ``a_mean`` rows; padded query rows are sliced off the
outputs), backend selection (interpret=True off-TPU), and the
hyper-parameter plumbing from the core library's log-space dict.

Precision contract: on TPU the kernel computes in f32 (MXU-native); under
interpret mode it keeps the caller's dtype, so the CI parity tests run the
exact f64 math of the XLA serving path.

Differentiation: none — prediction is a forward-only path (the serving
discipline), so unlike ``reg_stats``/``psi_stats`` there is no
``custom_vjp`` here.  Anything that needs gradients through a prediction
(e.g. the GPLVM reconstruction inner loop) uses the XLA
``serve.posterior.predict_mean_var`` instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import gp_kernels as gpk
from .._common import on_tpu as _on_tpu
from .._common import pad_to as _pad_to
from . import kernel as _k


@functools.partial(jax.jit, static_argnames=("block_t", "block_m", "interpret",
                                             "compute_dtype"))
def predict_stats(hyp: dict, z, a_mean, g, x, block_t: int = 128,
                  block_m: int = 128, interpret: bool | None = None,
                  compute_dtype=None):
    """Fused serving statistics via the Pallas kernel.

    Returns ``(mean, quad)``: ``ksm @ a_mean`` (t, d) and
    ``rowsum((ksm @ g) * ksm)`` (t,) — without materialising ``ksm`` in HBM.

    ``compute_dtype`` pins the tile dtype (the serving engines pass their
    accumulation width so quantized bf16/f16 states run f32 tiles rather
    than half-precision arithmetic); ``None`` keeps the historical default.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    t, d = x.shape[0], a_mean.shape[1]
    if compute_dtype is not None:
        # Caller-pinned width, clamped to what the backend runs: sub-f32
        # never reaches the tiles, and on TPU (non-interpret) the MXU
        # precision contract stays f32 even for an f64 request.
        dt = jnp.dtype(compute_dtype)
        if dt.itemsize < 4 or (not interpret and dt.itemsize > 4):
            dt = jnp.dtype(jnp.float32)
    else:
        # f32 on the MXU; caller dtype (f64 in this repo) under interpret.
        dt = x.dtype if interpret else jnp.float32
    inv_ell2 = jnp.exp(-2.0 * hyp["log_ell"]).astype(dt)[None, :]   # (1, q)
    sf2 = jnp.exp(hyp["log_sf2"]).astype(dt)[None, None]            # (1, 1)

    pad8 = 8
    inv_p = _pad_to(inv_ell2, pad8, 1, value=1.0)
    z_p = _pad_to(_pad_to(z.astype(dt), pad8, 1), block_m, 0)
    x_p = _pad_to(_pad_to(x.astype(dt), pad8, 1), block_t, 0)
    a_p = _pad_to(_pad_to(a_mean.astype(dt), pad8, 1), block_m, 0)
    g_p = _pad_to(_pad_to(g.astype(dt), block_m, 0), block_m, 1)

    mean, quad = _k.predict_pallas(inv_p, sf2, z_p, x_p, a_p, g_p,
                                   block_t=block_t, block_m=block_m,
                                   interpret=interpret)
    return mean[:t, :d], quad[:t, 0]


def predict_fn_for_engine(block_t: int = 128, block_m: int = 128,
                          compute_dtype=None, kernel=None):
    """Adapter matching serve.engine's per-block fn: (state, x) -> (mean, var).

    ``compute_dtype`` threads the engine's accumulation width into the tile
    dtype (see :func:`predict_stats`); outputs are returned in the query
    dtype either way.

    Dispatch shim for the compositional kernel layer: the fused Pallas
    kernel evaluates the SE-ARD cross-covariance in its tiles, so the
    full-width SE-ARD expression (the default) gets the fast path; any
    other expression falls back to the XLA serving math
    (``serve.posterior.predict_mean_var``) — same per-block contract.
    """
    from ...core.covariance import as_kernel, is_fused_se

    kernel = as_kernel(kernel)
    cdt = None if compute_dtype is None else jnp.dtype(compute_dtype)

    if not is_fused_se(kernel):
        def fn(state, x):
            from ...serve.posterior import predict_mean_var
            mean, var = predict_mean_var(state, x)
            return mean.astype(x.dtype), var.astype(x.dtype)

        return fn

    def fn(state, x):
        mean, quad = predict_stats(state.hyp, state.z, state.a_mean, state.g,
                                   x, block_t=block_t, block_m=block_m,
                                   compute_dtype=cdt)
        var = gpk.se_kdiag(state.hyp, x) - quad
        return mean.astype(x.dtype), var.astype(x.dtype)

    return fn
