"""Model operations of one training iteration, from shapes: the map's
forward and backward over the iteration's rows, the m x m global step
forward and backward, and Adam.  Recomputation is not counted.

The global step: chol(Kmm) and chol(Kmm + b D) (m^3/3 each), the two
solves for Kmm^-1 D and the solve for Sigma^-1 C (m^3 and m^2 d each as
2-flop multiply-adds: 2m^3 + 2m^2 d), Kmm itself (2m^2 q); its backward is
counted as twice its forward, the usual ratio for dense factorisations.
"""
from __future__ import annotations

from bench.work import psi2, reg_stats


def global_step(m: int, q: int, d: int) -> int:
    fwd = 2 * m ** 3 // 3 + 2 * m ** 3 + 2 * m * m * d + 2 * m * m * q
    return 3 * fwd


def latent_map(n: int, m: int, q: int, d: int) -> int:
    """psi2 (forward + backward), psi1 and C (forward, and twice that
    back), psi0, the KL and A."""
    p2 = psi2.forward(n, m, q)[0] + psi2.backward(n, m, q)
    psi1_c = n * m * (4 * q + 4) + 2 * n * m * d
    rest = n * (5 * q + 2 * d + 2)
    return p2 + 3 * (psi1_c + rest)


def flops(config: dict, rows: int) -> int:
    """Operations of one iteration over ``rows`` rows of ``config``."""
    m, q, d = config["m"], config["q"], config["d"]
    if config["model"] == "gplvm":
        body = latent_map(rows, m, q, d)
        params = 1 + q + 1 + m * q + rows * q
    else:
        body = (reg_stats.forward(rows, m, q, d)[0]
                + reg_stats.backward(rows, m, q, d) + 3 * rows * d)
        params = 1 + q + 1 + m * q
    return body + global_step(m, q, d) + 10 * params
