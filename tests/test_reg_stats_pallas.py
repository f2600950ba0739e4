"""Fused regression-stats Pallas kernel (interpret mode) vs the XLA path.

The fused kernel must be a bit-for-bit drop-in for the monolithic regression
map — same bound, same gradients — because under interpret mode off-TPU
both its forward and its backward kernel run the caller's f64 math, and
``stats.reg_stats_dense`` is the reference for both.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SGPR
from repro.core.bound import collapsed_bound
from repro.core.distributed import DistributedGP
from repro.core.stats import (partial_stats, partial_stats_chunked,
                              reg_stats_dense)
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


def test_f32_path_sums_d_as_an_exact_gram(rng, monkeypatch):
    """The TPU's f32 path sums D exactly from grid-rounded rows, so
    ``W = L^-1 D L^-T`` stays positive semi-definite where Kmm is as
    ill-conditioned as its jitter: the bound is finite and within 1e-6 of
    f64's. The same D summed in f32 has W's least eigenvalue near -190
    and the bound NaN. The f32 path is run in interpret mode here."""
    from repro.core.bound import _chol_kmm
    from repro.core.stats import Stats

    n, m, q = 4096, 30, 2
    x = jnp.asarray(rng.uniform(-2.0, 2.0, (n, q)))
    y = jnp.asarray(rng.standard_normal((n, 1)))
    z = jnp.asarray(rng.uniform(-1.5, 1.5, (m, q)))
    w = jnp.ones((n,))
    hyp = {"log_sf2": jnp.asarray(0.0), "log_ell": jnp.zeros((q,)),
           "log_beta": jnp.asarray(3.0)}
    f32_operands = rs_ops._operands
    monkeypatch.setattr(rs_ops, "_operands",
                        lambda bn, bm, _, *a: f32_operands(bn, bm, False, *a))
    b, c, d_grid = rs_ops._fwd_impl(128, 128, True, hyp, z, x, y, w)
    assert d_grid.dtype == jnp.float64
    b0, c0, d0 = reg_stats_dense(hyp, z, x, y, w)
    with jax.default_matmul_precision("highest"):
        d32 = reg_stats_dense(*jax.tree.map(
            lambda a: a.astype(jnp.float32), (hyp, z, x, y, w)))[2]
    chol = np.asarray(_chol_kmm(hyp, z, 1e-6, None))
    assert np.linalg.eigvalsh(chol @ chol.T)[0] < 1.1e-6   # Kmm at jitter

    def bound_and_least_eig(d_stat):
        li = np.linalg.inv(chol)
        ev = np.linalg.eigvalsh(li @ np.asarray(d_stat, np.float64) @ li.T)
        st = Stats(A=jnp.sum(y * y), B=b0, C=c0,
                   D=jnp.asarray(d_stat, jnp.float64), KL=jnp.zeros(()),
                   n=jnp.asarray(float(n)))
        return float(collapsed_bound(hyp, z, st, 1)), ev[0] / ev[-1]

    exact, _ = bound_and_least_eig(d0)
    grid, least = bound_and_least_eig(d_grid)
    assert np.isfinite(grid) and abs(grid - exact) < 1e-6 * abs(exact)
    assert least > -1e-12
    f32_sum, least32 = bound_and_least_eig(d32)
    assert np.isnan(f32_sum) and least32 < -1e-3
    np.testing.assert_allclose(np.asarray(d_grid), np.asarray(d0),
                               rtol=0, atol=1e-6 * float(jnp.max(d0)))


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


@pytest.mark.parametrize("n,m,q,d,block_n,block_m", [
    (100, 37, 3, 2, 32, 16),    # m, n, q fit no tile; three inducing tiles
    (64, 16, 8, 1, 64, 16),     # every tile fits exactly, q = 8
    (257, 130, 10, 5, 64, 128),  # q > 8, two inducing tiles, multi-output
    (40, 9, 1, 3, 16, 8),       # q = 1, m one past a tile
])
def test_reg_stats_bwd_parity(rng, n, m, q, d, block_n, block_m):
    """The fused backward kernel (interpret mode, the caller's f64) gives
    every cotangent ``jax.vjp(reg_stats_dense)`` gives, for random G and
    dC and rows of zero weight."""
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)
    cts = (jnp.asarray(rng.standard_normal()),
           jnp.asarray(rng.standard_normal((m, d))),
           jnp.asarray(rng.standard_normal((m, m))))
    got = rs_ops.reg_stats_bwd(hyp, z, x, y, w, cts, block_n=block_n,
                               block_m=block_m, interpret=True)
    _, vjp = jax.vjp(reg_stats_dense, hyp, z, x, y, w)
    want = vjp(cts)
    for a, b, name in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          ("log_beta", "log_ell", "log_sf2", "z", "x", "y",
                           "w")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-11, atol=1e-12, err_msg=name)


def test_reg_stats_bwd_bound_value_and_grad(rng):
    """value_and_grad of the bound through the chunked fused map, with
    respect to (hyp, z, x, y), against the XLA backend: masked rows and
    sizes that fit no tile."""
    n, m, q, d = 90, 11, 3, 2
    hyp = _hyp(rng, q)
    z, x, y, w = _mk(rng, n, m, q, d)

    def neg(h, zz, xx, yy, fused):
        st = partial_stats_chunked(
            h, zz, yy, xx, s=None, weights=w, latent=False,
            reg_stats_fn=rs_ops.reg_stats_fn_for_engine(16, 8) if fused
            else None, block_size=24)
        return -collapsed_bound(h, zz, st, d)

    outs = [jax.jit(jax.value_and_grad(
        lambda h, zz, xx, yy: neg(h, zz, xx, yy, fused),
        argnums=(0, 1, 2, 3)))(hyp, z, x, y) for fused in (False, True)]
    (v0, g0), (v1, g1) = outs
    assert abs(float(v1) - float(v0)) < 1e-10 * abs(float(v0))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-8, atol=1e-10)


def _eqns(jaxpr, into_kernels=False):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters,
    not entering a ``pallas_call``'s kernel unless asked."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, into_kernels)


def test_backward_scan_runs_the_fused_kernel(rng):
    """The engine's gradient program (``kernel_backend="pallas"``) runs the
    backward kernel in its reverse block scan and no float64 product of a
    (block, m) slab there: a fallback to the XLA recompute would show."""
    mesh = make_compat_mesh((1,), ("data",))
    n, m, q, d, chunk = 80, 12, 2, 1, 16
    x = rng.standard_normal((n, q))
    y = rng.standard_normal((n, d))
    z = jnp.asarray(rng.standard_normal((m, q)))
    eng = DistributedGP(mesh, data_axes=("data",), latent=False,
                        chunk_size=chunk, kernel_backend="pallas")
    data, w = eng.put_data(y=y, mu=x)
    vg = eng.make_value_and_grad(d)
    jaxpr = jax.make_jaxpr(vg)(_hyp(rng, q), z, data["mu"], None, data["y"],
                               w, jnp.ones((1,)), jnp.asarray(float(n)))

    def kernels(e):
        return {k.params["jaxpr"].debug_info.func_name
                for k in _eqns(e.params["jaxpr"].jaxpr)
                if k.primitive.name == "pallas_call"}

    scans = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "scan"]
    bwd = [e for e in scans if "_reg_stats_bwd_kernel" in kernels(e)]
    assert len(bwd) == 1, [kernels(e) for e in scans]
    assert kernels(bwd[0]) == {"_reg_stats_bwd_kernel"}
    slab = [k for k in _eqns(bwd[0].params["jaxpr"].jaxpr)
            if k.primitive.name == "dot_general"
            and any(v.aval.dtype == jnp.float64 and chunk in v.aval.shape
                    and m in v.aval.shape for v in k.invars)]
    assert not slab, slab
