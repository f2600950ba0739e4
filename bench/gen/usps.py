"""USPS-shaped digit images and the GPLVM's start (Gal et al. 2014 §4.5).

Copies of ``repro.data.synthetic.usps_like`` and of the PCA and k-means
initialisers in ``repro.core.init_utils``, so the benchmark's data stays
fixed when the program's code changes.  ``usps_images`` draws the same
random numbers in the same order as the program's generator; the strokes
are summed point by point in the same order, so the images are equal.
"""
from __future__ import annotations

import numpy as np


def usps_images(rng: np.random.Generator, n: int = 4649, side: int = 16):
    """``(Y in [0, 1]^(n, side**2), labels 0..9)``: one smooth stroke per
    image, its shape set by the class and two normal draws."""
    labels = rng.integers(0, 10, size=n)
    jitter = np.empty((n, 2))
    for i in range(n):                 # a then b, image by image
        jitter[i, 0] = rng.standard_normal()
        jitter[i, 1] = rng.standard_normal()
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float64) / (side - 1)
    c = labels.astype(np.float64)[:, None]
    t = np.linspace(0, 1, 40)[None, :]
    a = 0.6 + 0.04 * c + 0.02 * jitter[:, :1]
    b = 0.2 + 0.07 * c + 0.02 * jitter[:, 1:]
    cx = 0.5 + 0.35 * np.cos(2 * np.pi * (a * t + 0.1 * c))     # (n, 40)
    cy = 0.5 + 0.35 * np.sin(2 * np.pi * (b * t + 0.05 * c))
    imgs = np.zeros((n, side, side))
    for p in range(t.shape[1]):
        px, py = cx[:, p, None, None], cy[:, p, None, None]
        imgs += np.exp(-(((xx - px) ** 2 + (yy - py) ** 2) / 0.006))
    imgs /= imgs.max(axis=(1, 2), keepdims=True)
    return imgs.reshape(n, -1), labels


def pca(y: np.ndarray, q: int) -> np.ndarray:
    """Unit-variance principal components of ``y`` (n, d) -> (n, q)."""
    yc = y - y.mean(axis=0, keepdims=True)
    u, s, _ = np.linalg.svd(yc, full_matrices=False)
    x = u[:, :q] * s[:q]
    std = x.std(axis=0)
    std[std == 0] = 1.0
    return x / std


def kmeans(x: np.ndarray, k: int, iters: int, seed: int,
           noise: float = 1e-2) -> np.ndarray:
    """Lloyd's k-means centres plus a little noise (the paper's Z start)."""
    rng = np.random.default_rng(seed)
    centres = x[rng.choice(x.shape[0], size=k, replace=False)].copy()
    for _ in range(iters):
        assign = ((x[:, None, :] - centres[None]) ** 2).sum(-1).argmin(axis=1)
        for j in range(k):
            pts = x[assign == j]
            if len(pts):
                centres[j] = pts.mean(axis=0)
    return centres + noise * rng.standard_normal(centres.shape)


def gplvm_start(n: int, q: int, m: int, seed: int, kmeans_iters: int = 5):
    """The USPS GPLVM from the seed: images, PCA means, unit variances
    0.5, k-means inducing inputs, and hyper-parameters with unit
    lengthscales.  Returns ``(y, mu, s, hyp, z)`` as float64 numpy."""
    rng = np.random.default_rng(seed)
    y, _ = usps_images(rng, n=n)
    mu = pca(y, q)
    s = np.full_like(mu, 0.5)
    z = kmeans(mu, m, iters=kmeans_iters, seed=seed)
    var_y = float(np.var(y))
    hyp = {"log_sf2": np.float64(np.log(var_y)), "log_ell": np.zeros(q),
           "log_beta": np.float64(-np.log(0.01 * var_y))}
    return y, mu, s, hyp, z
