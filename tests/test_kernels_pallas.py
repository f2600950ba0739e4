"""Pallas kernel sweeps (interpret mode on CPU) vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.psi_stats import ops as ps_ops
from repro.kernels.psi_stats import ref as ps_ref


def _hyp(rng, q):
    return {"log_sf2": jnp.asarray(rng.uniform(-0.5, 0.8)),
            "log_ell": jnp.asarray(rng.uniform(-0.4, 0.4, q)),
            "log_beta": jnp.asarray(0.0)}


@pytest.mark.parametrize("n,m,q", [
    (64, 16, 2),     # tiny, exact tile fit after padding
    (100, 37, 3),    # nothing divides anything
    (257, 64, 10),   # q at paper-scale latent dim
    (32, 130, 1),    # m > block_m, q=1
])
def test_psi2_kernel_shapes(rng, n, m, q):
    hyp = _hyp(rng, q)
    z = jnp.asarray(rng.standard_normal((m, q)))
    mu = jnp.asarray(rng.standard_normal((n, q)))
    s = jnp.asarray(rng.uniform(0.05, 0.8, (n, q)))
    w = jnp.asarray((rng.uniform(size=n) > 0.1).astype(np.float64))
    out = ps_ops.psi2(hyp, z, mu, s, w, block_n=64, block_p=32)
    want = ps_ref.psi2_ref(hyp["log_sf2"], hyp["log_ell"], z, mu, s, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("n,m,q", [(64, 16, 2), (100, 37, 3), (130, 129, 5)])
def test_psi1_kernel_shapes(rng, n, m, q):
    hyp = _hyp(rng, q)
    z = jnp.asarray(rng.standard_normal((m, q)))
    mu = jnp.asarray(rng.standard_normal((n, q)))
    s = jnp.asarray(rng.uniform(0.0, 0.8, (n, q)))
    out = ps_ops.psi1(hyp, z, mu, s, block_n=64, block_m=64)
    want = ps_ref.psi1_ref(hyp["log_sf2"], hyp["log_ell"], z, mu, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-6)


def test_psi2_kernel_matches_core_engine_stats(rng):
    """Kernel is a drop-in for partial_stats' psi2_fn."""
    from repro.core.stats import partial_stats

    n, m, q, d = 90, 20, 2, 3
    hyp = _hyp(rng, q)
    z = jnp.asarray(rng.standard_normal((m, q)))
    mu = jnp.asarray(rng.standard_normal((n, q)))
    s = jnp.asarray(rng.uniform(0.05, 0.6, (n, q)))
    y = jnp.asarray(rng.standard_normal((n, d)))
    st_ref = partial_stats(hyp, z, y, mu, s=s, latent=True)
    st_k = partial_stats(hyp, z, y, mu, s=s, latent=True,
                         psi2_fn=ps_ops.psi2_fn_for_engine(64, 32))
    np.testing.assert_allclose(np.asarray(st_k.D), np.asarray(st_ref.D),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("log_ell,log_beta,conditioned", [
    (np.log(0.5), 0.0, True),
    (np.log(2.0), 0.0, False),   # Kmm too ill-conditioned for f32 D
])
def test_fused_psi2_bound_raises_when_bmat_is_indefinite(
        log_ell, log_beta, conditioned):
    """The engine's fused GPLVM bound matches the float64 XLA bound, also
    where Kmm is too ill-conditioned for a float32 D; and where a float32
    D (the fused kernel's D rounded) makes Bmat indefinite, the bound
    raises instead of returning NaN."""
    import jax

    from repro.core import DistributedGP
    from repro.launch.mesh import make_compat_mesh

    rng = np.random.default_rng(0)
    n, q, m, d = 128, 2, 24, 3
    mu, y = rng.standard_normal((n, q)), rng.standard_normal((n, d))
    z = jnp.asarray(rng.standard_normal((m, q)))
    hyp = {"log_sf2": jnp.asarray(0.0), "log_ell": jnp.full((q,), log_ell),
           "log_beta": jnp.asarray(log_beta)}
    mesh = make_compat_mesh((1,), ("data",))

    def rounded(*args):
        return ps_ops.psi2(*args).astype(jnp.float32).astype(jnp.float64)

    def bound(backend, psi2_fn=None):
        eng = DistributedGP(mesh, latent=True, chunk_size=64,
                            kernel_backend=backend, psi2_fn=psi2_fn)
        data, w = eng.put_data(y=y, mu=mu, s=np.full((n, q), 0.5))
        return float(jax.jit(eng.bound_fn(d))(
            hyp, z, data["y"], data["mu"], data["s"], w, jnp.ones((1,)),
            jnp.asarray(float(n))))

    want = bound("xla")
    assert np.isfinite(want)
    np.testing.assert_allclose(bound("pallas"), want, rtol=1e-10)
    if conditioned:
        np.testing.assert_allclose(bound("pallas", rounded), want, rtol=1e-5)
    else:
        with pytest.raises(Exception, match="not positive definite"):
            bound("pallas", rounded)


@pytest.mark.parametrize("b,h,hkv,t,s,dh,causal,dtype", [
    (2, 4, 2, 64, 64, 64, True, jnp.float32),
    (1, 8, 1, 70, 70, 64, True, jnp.float32),      # MQA, ragged t
    (1, 4, 4, 33, 90, 128, True, jnp.float32),     # cross t<s suffix align
    (2, 2, 2, 96, 48, 64, False, jnp.float32),     # non-causal, t>s
    (1, 4, 2, 64, 64, 64, True, jnp.bfloat16),     # bf16 path
    (1, 4, 4, 1, 57, 64, True, jnp.float32),       # decode-shaped (T=1)
])
def test_flash_attention_sweep(rng, b, h, hkv, t, s, dh, causal, dtype):
    q = jnp.asarray(rng.standard_normal((b, h, t, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, dh)), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=causal,
                                 block_q=32, block_k=32)
    want = fa_ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_rows_with_no_context(rng):
    """Fully-masked rows (can happen with padding) return zeros, not NaN."""
    q = jnp.asarray(rng.standard_normal((1, 2, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 8, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 8, 64)), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert np.isfinite(np.asarray(out)).all()
