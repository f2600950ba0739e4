"""psi2 to float64 accuracy: the compensated kernel (``kernels/psi_stats``,
interpret mode here) against the float64 closed form where the bound
amplifies psi2's errors most, and the engine's latent value and gradient
against the benchmark's independent float64 reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401  (float64 on)
from repro.core import gp_kernels as gpk
from repro.kernels.psi_stats import kernel as psi_kernel
from repro.kernels.psi_stats import ops as psi_ops
from repro.kernels.psi_stats import ref as psi_ref


@pytest.fixture(scope="module")
def ill_conditioned():
    """512 rows of the USPS GPLVM's start (PCA means, k-means z, unit
    lengthscales), where Kmm's least eigenvalue is ~3e-5 of sf2."""
    from bench.gen.usps import gplvm_start

    y, mu, s, hyp, z = gplvm_start(4649, 10, 150, 2147483922)
    hyp = {k: jnp.asarray(v) for k, v in hyp.items()}
    sf2 = float(np.exp(hyp["log_sf2"]))
    kmm = sf2 * np.exp(-0.5 * ((z[:, None] - z[None]) ** 2).sum(-1))
    kmm += 1e-6 * np.eye(z.shape[0])
    assert np.linalg.eigvalsh(kmm)[0] / sf2 <= 1e-4
    args = (hyp, jnp.asarray(z), jnp.asarray(mu[:512]),
            jnp.asarray(s[:512]), jnp.ones(512))
    want = np.asarray(psi_ref.psi2_ref(hyp["log_sf2"], hyp["log_ell"],
                                       *args[1:]))
    return args, want, np.linalg.inv(np.linalg.cholesky(kmm))


def test_psi2_kernel_holds_float64_accuracy_where_kmm_is_ill_conditioned(
        ill_conditioned):
    """The bound reads psi2 through L^-1 psi2 L^-T, which multiplies an
    independent per-entry error by up to 1 / lambda_min(Kmm) (3e4 here).
    One f32 rounding per entry (the float32 kernel) reads ~7e-3 on it
    here, and moved the USPS gradients by up to 1e-1 on the chip;
    1e-8 keeps the gradients at float64 level, and the compensated kernel
    reads ~2e-10 (per entry ~1e-13: 1e-12 leaves room)."""
    args, want, l_inv = ill_conditioned
    got = np.asarray(psi_ops.psi2(*args, interpret=True))
    assert np.max(np.abs(got - want) / np.abs(want)) < 1e-12

    def whitened(d):
        return l_inv @ d @ l_inv.T

    err = np.linalg.norm(whitened(got) - whitened(want)) / np.linalg.norm(
        whitened(want))
    assert err < 1e-8, err


def test_psi2_exp_dd_is_accurate_to_1e13():
    """The kernel's exponential of an f32 pair, over the exponents whose
    terms a psi2 sum can hold (below -60 the low half underflows and the
    term keeps f32 accuracy; it is under e^-60 of the largest)."""
    rng = np.random.default_rng(0)
    x = np.concatenate([-rng.uniform(0.0, 60.0, 100_000),
                        -rng.uniform(0.0, 1.0, 10_000), [0.0, -1e-30]])
    xh = x.astype(np.float32)
    xl = (x - xh).astype(np.float32)
    fact = jnp.asarray(psi_kernel.INV_FACT_DD[0])
    coef = [(fact[2 * j], fact[2 * j + 1]) for j in range(6)]
    hi, lo = jax.jit(psi_kernel._exp_dd)(jnp.asarray(xh), jnp.asarray(xl),
                                         coef)
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    want = np.exp(xh.astype(np.float64) + xl)
    assert np.max(np.abs(got - want) / want) < 1e-12
    assert float(psi_kernel._exp_dd(jnp.float32(-88.0), jnp.float32(0.0),
                                    coef)[0]) == 0.0


def test_gplvm_engine_matches_the_float64_reference_over_three_steps():
    """The engine (fused psi2, f64 psi1/C, fold and global step) against
    ``bench/reference`` (plain f64 jnp, its own Adam) on MNIST-shaped
    rows, n=512, d=784, q=10, m=16, three Adam steps, compared as the
    benchmark compares them.  Everything but the kernel is f64 on the
    CPU; with the compensated kernel the readings are 2.5e-15 (loss),
    1.8e-14 (first gradient) and 5.9e-16 (change), with the float32
    kernel 1.9e-8, 4.4e-9 and 1.9e-8.  Each limit is ~1000x the first."""
    from bench import compare, harness
    from bench.reference import training as reference
    from bench.traffic import gplvm_exact

    config = {"n": 512, "d": 784, "q": 10, "m": 16, "jitter": 1e-6,
              "chunk_size": 128, "kmeans_iters": 5,
              "optimizer": {"lr": 0.01}}
    mix = {"mesh": 1, "reduce_mode": "serial"}
    drv = gplvm_exact.Runner(config, mix, 2**31 + 12345, jax.devices()[:1])
    prog = harness.checked_steps(drv)
    ref = reference.train(drv.start, drv.reference_feeds(3), d=784,
                          jitter=1e-6, block=64, lr=0.01)
    got = compare.readings(prog, ref)
    assert got["loss_rel"] < 1e-12, got
    assert got["grad1_norm"] < 1e-11, got
    assert got["change_norm"] < 1e-12, got


def test_psi2_backward_over_the_pairs_matches_the_closed_form():
    """The backward recomputes over the pairs a <= b only, each pair's
    cotangent the sum of its two entries'; against autodiff of the f64
    closed form with an unsymmetric cotangent, for every input (both are
    f64: 1e-10 leaves room for the two forms' rounding)."""
    rng = np.random.default_rng(5)
    n, m, q = 70, 13, 3
    hyp = {"log_sf2": jnp.asarray(0.3), "log_ell": jnp.asarray(
        rng.uniform(-0.4, 0.4, q)), "log_beta": jnp.asarray(0.0)}
    args = (hyp, jnp.asarray(rng.standard_normal((m, q))),
            jnp.asarray(rng.standard_normal((n, q))),
            jnp.asarray(rng.uniform(0.05, 0.8, (n, q))),
            jnp.asarray((rng.uniform(size=n) > 0.2).astype(np.float64)))
    ct = jnp.asarray(rng.standard_normal((m, m)))

    def ref(h, *rest):
        return psi_ref.psi2_ref(h["log_sf2"], h["log_ell"], *rest)

    got = jax.vjp(lambda *a: psi_ops.psi2(*a, interpret=True), *args)[1](ct)
    want = jax.vjp(ref, *args)[1](ct)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-10, atol=1e-12)


def _worst_leaf(got, want):
    """The largest leaf error relative to the leaf's norm, over the leaves
    with a cotangent."""
    return max(np.linalg.norm(np.asarray(g) - np.asarray(r))
               / np.linalg.norm(np.asarray(r))
               for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want))
               if np.linalg.norm(np.asarray(r)) > 0.0)


def test_psi2_backward_kernel_holds_float64_accuracy_on_a_whitened_cotangent(
        ill_conditioned):
    """The backward kernel (``ops.psi2_bwd``) against autodiff of the f64
    ``gp_kernels.psi2_mxu`` where Kmm is ill-conditioned, with the shape of
    cotangent the bound feeds back, ``L^-T X L^-1`` (X random, symmetric):
    every leaf within 1e-8 of its norm, the bar the forward's whitened
    test holds psi2 to.  It reads 1.2e-9 (mu; log_sf2 2.3e-11).  The same
    kernel with its sums in plain f32 (each ``_add`` one rounded add)
    reads 2.7e-3 (log_sf2), so the limit tells the two apart."""
    args, _, l_inv = ill_conditioned
    x = np.random.default_rng(7).standard_normal(l_inv.shape)
    ct = jnp.asarray(l_inv.T @ (x + x.T) @ l_inv)
    want = jax.vjp(gpk.psi2_mxu, *args)[1](ct)
    err = _worst_leaf(psi_ops.psi2_bwd(*args, ct, block_n=256, block_p=256,
                                       interpret=True), want)
    assert err < 1e-8, err

    def plain_add(a, b):
        s = a[0] + b[0]
        return s, jnp.zeros_like(s)

    compensated = psi_kernel._add
    psi_kernel._add = plain_add
    try:        # unjitted, so the kernel is traced again with plain_add
        plain = _worst_leaf(psi_ops.psi2_bwd.__wrapped__(
            *args, ct, block_n=256, block_p=256, interpret=True), want)
    finally:
        psi_kernel._add = compensated
    assert plain >= 100 * err, (plain, err)


@pytest.mark.parametrize("block_n, block_p, m, q", [
    (8, 128, 13, 3), (16, 128, 20, 3), (72, 256, 20, 3), (16, 32, 20, 3),
    (8, 128, 13, 33)],
    ids=["8-128-13", "16-128-20", "72-256-20", "16-32-20", "8-128-13-q33"])
def test_psi2_backward_kernel_tiles_and_padding(block_n, block_p, m, q):
    """Every cotangent of ``ops.psi2`` (the backward kernel at the op's
    tiles) against autodiff of the f64 closed form, over row and pair
    tiles: 70 rows, a fifth with w = 0, padded to ``block_n`` with more;
    m(m + 1)/2 = 91 or 210 pairs, padded to ``block_p`` (rounded up to
    128 lanes); q = 33 puts the 2q + 1 row sums in two lane blocks
    (1e-10, as the pairs test above)."""
    rng = np.random.default_rng(11)
    n = 70
    hyp = {"log_sf2": jnp.asarray(-0.2), "log_ell": jnp.asarray(
        rng.uniform(-0.4, 0.4, q) + 0.5 * np.log(q / 3)),
        "log_beta": jnp.asarray(0.0)}
    args = (hyp, jnp.asarray(rng.standard_normal((m, q))),
            jnp.asarray(rng.standard_normal((n, q))),
            jnp.asarray(rng.uniform(0.05, 0.8, (n, q))),
            jnp.asarray((rng.uniform(size=n) > 0.2).astype(np.float64)))
    ct = jnp.asarray(rng.standard_normal((m, m)))

    def ref(h, *rest):
        return psi_ref.psi2_ref(h["log_sf2"], h["log_ell"], *rest)

    def kernel(*a):
        return psi_ops.psi2(*a, block_n=block_n, block_p=block_p,
                            interpret=True)

    got = jax.vjp(kernel, *args)[1](ct)
    want = jax.vjp(ref, *args)[1](ct)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-10, atol=1e-12)
