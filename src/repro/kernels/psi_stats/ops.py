"""jit'd public wrappers around the psi-statistics Pallas kernels.

Handles padding to tile boundaries (all pads are NEUTRAL — padded data
rows carry w=0; padded inducing rows are sliced off the output; psi1's
padded latent dims carry mu=s=z=0, ell2=1), the operand layouts the
kernels want, backend selection (interpret=True off-TPU), and the
hyper-parameter plumbing from the core library's log-space dict.

``psi2`` carries a ``custom_vjp`` (``pallas_call`` has no VJP on this JAX
version): forward is the Pallas kernel, backward recomputes through the
MXU-matmul XLA reformulation (``gp_kernels.psi2_mxu``) — so the kernel can
sit inside ``jax.grad`` of the bound (the engine's ``kernel_backend=
"pallas"`` path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import gp_kernels as gpk
from .._common import on_tpu as _on_tpu
from .._common import pad_to as _pad_to
from . import kernel as _k


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _psi2(block_n, block_m, interpret, hyp, z, mu, s, w):
    return _psi2_fwd_impl(block_n, block_m, interpret, hyp, z, mu, s, w)


def _psi2_fwd_impl(block_n, block_m, interpret, hyp, z, mu, s, w):
    # Everything outside the kernel runs in the caller's dtype (f64 here):
    # the TPU's f32 log/exp would cost more accuracy than the kernel does.
    m = z.shape[0]
    f32 = jnp.float32
    ell2 = jnp.exp(2.0 * hyp["log_ell"])                            # (q,)
    den = ell2 + 2.0 * s                                             # (n, q)
    inv_den = 1.0 / den
    lognorm = -0.5 * jnp.sum(jnp.log(den / ell2), axis=1, keepdims=True)
    alpha = lognorm - jnp.sum(mu * mu * inv_den, axis=1, keepdims=True)
    mmat = jnp.concatenate([2.0 * mu * inv_den, -inv_den], axis=1)  # (n, 2q)

    # Padded rows are all zeros (exponent 0) and carry w = 0.
    z_p = _pad_to(z, block_m, 0)
    zbar = 0.5 * (z_p[:, None, :] + z_p[None, :, :])        # (m_pad, m_pad, q)
    zb = jnp.concatenate([zbar, zbar * zbar], axis=-1)      # (m_pad, m_pad, 2q)
    out = _k.psi2_pallas(zb.astype(f32),
                         _pad_to(mmat.astype(f32), block_n, 0),
                         _pad_to(alpha.astype(f32), block_n, 0),
                         _pad_to(w.astype(f32)[:, None], block_n, 0),
                         block_n=block_n, block_m=block_m,
                         interpret=interpret)
    # The i-free factor of every term: sf4 * exp(-1/4 sum_q dz^2 / ell2).
    dz = z[:, None, :] - z[None, :, :]
    static = -0.25 * jnp.sum(dz * dz / ell2, axis=-1)               # (m, m)
    sf4 = jnp.exp(2.0 * hyp["log_sf2"])
    return sf4 * jnp.exp(static) * out[:m, :m].astype(static.dtype)


def _psi2_vjp_fwd(block_n, block_m, interpret, hyp, z, mu, s, w):
    out = _psi2_fwd_impl(block_n, block_m, interpret, hyp, z, mu, s, w)
    return out, (hyp, z, mu, s, w)


def _psi2_vjp_bwd(block_n, block_m, interpret, res, ct):
    del block_n, block_m, interpret
    # Backward recompute via the XLA MXU reformulation; chunk=256 bounds the
    # live (chunk, m^2) intermediate under the streaming engine's blocks.
    out, vjp = jax.vjp(
        lambda h, zz, mm, ss, ww: gpk.psi2_mxu(h, zz, mm, ss, ww, chunk=256),
        *res)
    return vjp(jnp.asarray(ct, out.dtype))


_psi2.defvjp(_psi2_vjp_fwd, _psi2_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def psi2(hyp: dict, z, mu, s, w, block_n: int = 128, block_m: int = 128,
         interpret: bool | None = None):
    """Weighted Psi2 = sum_i w_i <K_mi K_im> via the Pallas kernel. (m, m)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _psi2(block_n, block_m, interpret, hyp, z, mu, s, w)


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


def psi2_fn_for_engine(block_n: int = 128, block_m: int = 128, kernel=None):
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
            return psi2(hyp, z, mu, s, w, block_n=block_n, block_m=block_m)
    else:
        def fn(hyp, z, mu, s, w):
            return kernel.psi2(hyp, z, mu, s, w)

    return fn
