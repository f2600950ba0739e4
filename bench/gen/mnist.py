"""MNIST-shaped digit images and the GPLVM's start (Gal et al. 2014 §5).

The real MNIST file is not in the repository: ``mnist_images`` draws
60,000 28 x 28 images in [0, 1] from the seed, one smooth stroke each,
its shape set by the class and seven normal draws (frequencies, slant,
size, place, width), about the 20 x 20 box MNIST centres its digits in,
the ink saturating and the background exactly 0.  A stroke point is a
round Gaussian blot, which is separable: ``exp(-((x - px)^2 + (y -
py)^2) / w) = gx gy^T``, so an image is one (28, P) x (P, 28) product of
its points' row and column profiles, and the whole set is made in a few
batched matrix products rather than one ``exp`` per pixel and point.

The start is that of ``bench/gen/usps.py`` (unit-variance PCA means,
variances 0.5, k-means inducing inputs, unit lengthscales), with a
k-means that holds (n, m) distances and never an (n, m, q) difference.
"""
from __future__ import annotations

import numpy as np

from bench.gen.usps import pca

SIDE = 28
POINTS = 48            # points along a digit's strokes
BLOCK = 8192           # images made per batched product


def _profiles(centre, width, side):
    """(rows, points, side) Gaussian profiles of points at ``centre``."""
    grid = np.arange(side, dtype=np.float64)
    return np.exp(-(grid[None, None, :] - centre[..., None]) ** 2
                  / width[..., None])


def mnist_images(rng: np.random.Generator, n: int = 60000,
                 side: int = SIDE):
    """``(Y in [0, 1]^(n, side**2), labels 0..9)``."""
    labels = rng.integers(0, 10, size=n)
    draws = rng.standard_normal((n, 7))
    c = labels.astype(np.float64)[:, None]
    t = np.linspace(0.0, 1.0, POINTS)[None, :]
    # the class sets the curve's frequencies and phase; the draws jitter
    # them, the slant, the size, the place and the stroke's width
    fa = 0.55 + 0.05 * c + 0.03 * draws[:, :1]
    fb = 0.25 + 0.08 * c + 0.03 * draws[:, 1:2]
    slant = 0.15 * draws[:, 2:3]
    size = 7.0 * (1.0 + 0.08 * draws[:, 3:4])
    mid = side / 2.0 - 0.5 + 0.8 * draws[:, 4:6]
    u = np.cos(2 * np.pi * (fa * t + 0.1 * c))
    v = np.sin(2 * np.pi * (fb * t + 0.05 * c))
    px = mid[:, :1] + size * (u + slant * v)                # (n, POINTS)
    py = mid[:, 1:2] + size * v
    width = np.broadcast_to(2.5 * (1.0 + 0.15 * np.abs(draws[:, 6:7])),
                            px.shape)
    imgs = np.empty((n, side, side))
    for a in range(0, n, BLOCK):
        b = min(a + BLOCK, n)
        gy = _profiles(py[a:b], width[a:b], side)           # (rows, P, side)
        gx = _profiles(px[a:b], width[a:b], side)
        imgs[a:b] = np.matmul(gy.transpose(0, 2, 1), gx)
    # ink saturates where strokes overlap; the background is exactly 0
    imgs = 1.0 - np.exp(-1.5 * imgs)
    imgs[imgs < 1e-3] = 0.0
    return imgs.reshape(n, -1), labels


def kmeans(x: np.ndarray, k: int, iters: int, seed: int,
           noise: float = 1e-2) -> np.ndarray:
    """Lloyd's k-means centres plus a little noise, as ``usps.kmeans``,
    with squared distances |x|^2 - 2 x c + |c|^2 held as (n, k)."""
    rng = np.random.default_rng(seed)
    centres = x[rng.choice(x.shape[0], size=k, replace=False)].copy()
    for _ in range(iters):
        dist = (x * x).sum(1)[:, None] - 2.0 * x @ centres.T \
            + (centres * centres).sum(1)[None, :]
        assign = dist.argmin(axis=1)
        counts = np.bincount(assign, minlength=k)
        sums = np.zeros_like(centres)
        np.add.at(sums, assign, x)
        full = counts > 0
        centres[full] = sums[full] / counts[full, None]
    return centres + noise * rng.standard_normal(centres.shape)


def gplvm_start(n: int, q: int, m: int, seed: int, kmeans_iters: int = 5):
    """The MNIST GPLVM from the seed: images, PCA means, variances 0.5,
    k-means inducing inputs, and hyper-parameters with unit lengthscales
    (log_sf2 = log var Y, log_beta = -log(0.01 var Y)).  Returns ``(y,
    mu, s, hyp, z)`` as float64 numpy."""
    rng = np.random.default_rng(seed)
    y, _ = mnist_images(rng, n=n)
    mu = pca(y, q)
    s = np.full_like(mu, 0.5)
    z = kmeans(mu, m, iters=kmeans_iters, seed=seed)
    var_y = float(np.var(y))
    hyp = {"log_sf2": np.float64(np.log(var_y)), "log_ell": np.zeros(q),
           "log_beta": np.float64(-np.log(0.01 * var_y))}
    return y, mu, s, hyp, z
