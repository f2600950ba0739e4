"""Fused regression-stats Pallas kernel (interpret mode) vs the XLA path.

The fused kernel must be a bit-for-bit drop-in for the monolithic regression
map — same bound, same gradients — because under interpret mode off-TPU it
runs the caller's f64 math and its custom_vjp backward recomputes through
the exact XLA formulation of ``stats.partial_stats``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SGPR
from repro.core.bound import collapsed_bound
from repro.core.distributed import DistributedGP
from repro.core.stats import partial_stats, partial_stats_chunked
from repro.kernels.reg_stats import ops as rs_ops
from repro.kernels.reg_stats import ref as rs_ref
from repro.launch.mesh import make_compat_mesh

from conftest import make_regression


def _hyp(rng, q):
    return {"log_sf2": jnp.asarray(rng.uniform(-0.5, 0.8)),
            "log_ell": jnp.asarray(rng.uniform(-0.4, 0.4, q)),
            "log_beta": jnp.asarray(1.0)}


def _mk(rng, n, m, q, d, masked=True):
    z = jnp.asarray(rng.standard_normal((m, q)))
    x = jnp.asarray(rng.standard_normal((n, q)))
    y = jnp.asarray(rng.standard_normal((n, d)))
    w = (jnp.asarray((rng.uniform(size=n) > 0.15).astype(np.float64))
         if masked else jnp.ones((n,)))
    return z, x, y, w


@pytest.mark.parametrize("n,m,q,d", [
    (64, 16, 2, 1),     # exact tile fit after padding
    (100, 37, 3, 2),    # nothing divides anything
    (257, 64, 10, 5),   # q at paper-scale latent dim, multi-output
    (32, 130, 1, 3),    # m > block_m, q=1
])
def test_reg_stats_kernel_shapes(rng, n, m, q, d):
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)
    b, c, dd = rs_ops.reg_stats(hyp, z, x, y, w, block_n=64, block_m=32)
    rb, rc, rd = rs_ref.reg_stats_ref(hyp["log_sf2"], hyp["log_ell"],
                                      z, x, y, w)
    # Interpret mode runs the caller's f64 — machine-precision agreement.
    np.testing.assert_allclose(float(b), float(rb), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(c), np.asarray(rc),
                               rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd),
                               rtol=1e-12, atol=1e-14)


def test_reg_stats_f32_path(rng):
    """The TPU-precision (f32 compute) path, exercised via f32 inputs."""
    n, m, q, d = 96, 24, 3, 2
    hyp = {k: v for k, v in _hyp(rng, q).items()}
    z, x, y, w = _mk(rng, n, m, q, d)
    f32 = jnp.float32
    b, c, dd = rs_ops.reg_stats(
        {k: v.astype(f32) for k, v in hyp.items()},
        z.astype(f32), x.astype(f32), y.astype(f32), w.astype(f32),
        block_n=32, block_m=16)
    assert c.dtype == f32 and dd.dtype == f32
    rb, rc, rd = rs_ref.reg_stats_ref(hyp["log_sf2"], hyp["log_ell"],
                                      z, x, y, w)
    np.testing.assert_allclose(float(b), float(rb), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(c, np.float64), np.asarray(rc),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dd, np.float64), np.asarray(rd),
                               rtol=2e-4, atol=2e-5)


def test_tpu_branch_folds_blocks_in_caller_dtype(rng):
    """On the TPU the kernel computes in f32 but hands back the caller's
    f64, so the chunked map folds its blocks in f64: an f32 carry loses
    ~1e-6 over 1000 blocks and depends on how rows are split over shards.
    Abstract evaluation only — the TPU branch is not lowered here."""
    n, m, q, d = 96, 24, 3, 2
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)

    def fused(hyp, z, x, y, w):
        return rs_ops.reg_stats(hyp, z, x, y, w, interpret=False)

    st = jax.eval_shape(lambda: partial_stats_chunked(
        hyp, z, y, x, s=None, weights=w, latent=False, reg_stats_fn=fused,
        block_size=32))
    assert all(t.dtype == jnp.float64 for t in st), st


def test_partial_stats_hook_parity(rng):
    """reg_stats_fn plugs into partial_stats and reproduces every statistic,
    including with masked (zero-weight) rows."""
    n, m, q, d = 77, 12, 2, 3
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)
    st_ref = partial_stats(hyp, z, y, x, s=None, weights=w, latent=False)
    st_k = partial_stats(hyp, z, y, x, s=None, weights=w, latent=False,
                         reg_stats_fn=rs_ops.reg_stats_fn_for_engine(32, 8))
    for name, a, b in zip(st_ref._fields, st_ref, st_k):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-12, atol=1e-14, err_msg=name)


def test_chunked_hook_non_multiple_blocks(rng):
    """Fused kernel under partial_stats_chunked with a block size that
    divides neither n nor the kernel tiles."""
    n, m, q, d = 53, 9, 2, 2
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)
    full = partial_stats(hyp, z, y, x, s=None, weights=w, latent=False)
    ch = partial_stats_chunked(
        hyp, z, y, x, s=None, weights=w, latent=False,
        reg_stats_fn=rs_ops.reg_stats_fn_for_engine(16, 8), block_size=13)
    for name, a, b in zip(full._fields, full, ch):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-10, atol=1e-12, err_msg=name)


def test_bound_and_grad_parity(rng):
    """Bound + (hyp, Z) gradients through the fused chunked map match the
    monolithic XLA path to float64 precision (the custom_vjp contract)."""
    n, m, q, d = 60, 7, 2, 2
    x, y = make_regression(rng, n=n, q=q, d=d)
    z = rng.standard_normal((m, q))
    hyp = _hyp(rng, q)

    def neg(h, zz, fused):
        fn = rs_ops.reg_stats_fn_for_engine(16, 8) if fused else None
        st = partial_stats_chunked(h, zz, jnp.asarray(y), jnp.asarray(x),
                                   s=None, latent=False, reg_stats_fn=fn,
                                   block_size=16 if fused else None)
        return -collapsed_bound(h, zz, st, d)

    v0, (gh0, gz0) = jax.value_and_grad(
        lambda h, zz: neg(h, zz, False), argnums=(0, 1))(hyp, jnp.asarray(z))
    v1, (gh1, gz1) = jax.jit(jax.value_and_grad(
        lambda h, zz: neg(h, zz, True), argnums=(0, 1)))(hyp, jnp.asarray(z))
    assert abs(float(v1) - float(v0)) < 1e-8 * abs(float(v0))
    np.testing.assert_allclose(np.asarray(gz1), np.asarray(gz0),
                               rtol=1e-8, atol=1e-10)
    for k in gh0:
        np.testing.assert_allclose(np.asarray(gh1[k]), np.asarray(gh0[k]),
                                   rtol=1e-8, atol=1e-10, err_msg=k)


def test_sgpr_kernel_backend_parity(rng):
    x, y = make_regression(rng, n=70, q=2, d=2)
    xla = SGPR(x, y, num_inducing=10, seed=0)
    fused = SGPR(x, y, num_inducing=10, seed=0, chunk_size=16,
                 kernel_backend="pallas")
    np.testing.assert_allclose(fused.log_bound(), xla.log_bound(), rtol=1e-10)
    mean0, _ = xla.predict(x[:5])
    mean1, _ = fused.predict(x[:5])
    np.testing.assert_allclose(mean1, mean0, rtol=1e-8, atol=1e-10)


def test_sgpr_rejects_unknown_backend(rng):
    x, y = make_regression(rng, n=20, q=2, d=1)
    with pytest.raises(ValueError, match="kernel_backend"):
        SGPR(x, y, num_inducing=4, kernel_backend="cuda")


def test_distributed_kernel_backend_parity(rng):
    """kernel_backend='pallas' through DistributedGP: value AND grads of the
    shard_map program match the xla engine on a 1-device mesh."""
    mesh = make_compat_mesh((1,), ("data",))
    n, m, q, d = 37, 5, 2, 1
    x = rng.standard_normal((n, q)); y = rng.standard_normal((n, d))
    z = jnp.asarray(rng.standard_normal((m, q)))
    hyp = _hyp(rng, q)
    outs = {}
    for backend in ("xla", "pallas"):
        eng = DistributedGP(mesh, data_axes=("data",), latent=False,
                            chunk_size=8, kernel_backend=backend)
        data, w = eng.put_data(y=y, mu=x)
        vg = eng.make_value_and_grad(d)
        outs[backend] = vg(hyp, z, data["mu"], None, data["y"], w,
                           jnp.ones((1,)), jnp.asarray(float(n)))
    (v0, (gh0, gz0)), (v1, (gh1, gz1)) = outs["xla"], outs["pallas"]
    assert abs(float(v1) - float(v0)) < 1e-10 * max(1.0, abs(float(v0)))
    np.testing.assert_allclose(np.asarray(gz1), np.asarray(gz0),
                               rtol=1e-8, atol=1e-10)
    for k in gh0:
        np.testing.assert_allclose(np.asarray(gh1[k]), np.asarray(gh0[k]),
                                   rtol=1e-8, atol=1e-10, err_msg=k)


def test_make_gp_train_step_pallas_backend(rng):
    from repro.train.steps import make_gp_train_step

    mesh = make_compat_mesh((1,), ("data",))
    n, m, q, d = 24, 4, 2, 1
    x = rng.standard_normal((n, q)); y = rng.standard_normal((n, d))
    z = rng.standard_normal((m, q))
    eng, step = make_gp_train_step(mesh, d, chunk_size=8,
                                   kernel_backend="pallas")
    assert eng.reg_stats_fn is not None
    data, w = eng.put_data(y=y, mu=x)
    hyp = {"log_sf2": jnp.asarray(0.2), "log_ell": jnp.full((q,), 0.1),
           "log_beta": jnp.asarray(1.0)}
    v, (gh, gz) = step(hyp, jnp.asarray(z), data["mu"], None, data["y"], w,
                       jnp.ones((1,)), jnp.asarray(float(n)))
    assert np.isfinite(float(v))
    assert np.isfinite(np.asarray(gz)).all()


def test_latent_pallas_backend_grads(rng):
    """The pallas backend is grad-safe on the GPLVM path too (psi2's
    custom_vjp): engine grads match the xla backend."""
    mesh = make_compat_mesh((1,), ("data",))
    n, m, q, d = 21, 4, 2, 2
    y = rng.standard_normal((n, d))
    mu = rng.standard_normal((n, q)); s = rng.uniform(0.1, 0.5, (n, q))
    z = jnp.asarray(rng.standard_normal((m, q)))
    hyp = _hyp(rng, q)
    outs = {}
    for backend in ("xla", "pallas"):
        eng = DistributedGP(mesh, data_axes=("data",), latent=True,
                            chunk_size=8, kernel_backend=backend)
        data, w = eng.put_data(y=y, mu=mu, s=s)
        vg = eng.make_value_and_grad(d)
        outs[backend] = vg(hyp, z, data["mu"], data["s"], data["y"], w,
                           jnp.ones((1,)), jnp.asarray(float(n)))
    (v0, (gh0, gz0)), (v1, (gh1, gz1)) = outs["xla"], outs["pallas"]
    # psi2's Pallas forward runs in f32, so value parity is f32-level.
    assert abs(float(v1) - float(v0)) < 1e-4 * max(1.0, abs(float(v0)))
    np.testing.assert_allclose(np.asarray(gz1), np.asarray(gz0),
                               rtol=1e-4, atol=1e-6)
    for k in gh0:
        np.testing.assert_allclose(np.asarray(gh1[k]), np.asarray(gh0[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)
