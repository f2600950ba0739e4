"""Operations and bytes of the GPLVM's psi2 statistic, from the
algorithm's shapes.

psi2[i, j] = sum_n w_n sf2^2 prod_q (...) exp(E[n, ij]), where E's q-sum of
(mu - zbar_ij)^2 / (l2 + 2 s) is a 2q-long dot product per (n, i, j) pair
once the square is expanded (its direct form costs the same 4q).  Per
(n, i, j): 4q for E, 1 exp, 2 for the weighted sum.  Bytes: mu, s (n, q),
w (n), z (m, q) read once and the (m, m) result written once, float32.
"""
from __future__ import annotations


def forward(n: int, m: int, q: int, itemsize: int = 4):
    flops = n * m * m * (4 * q + 3)
    nbytes = itemsize * (n * (2 * q + 1) + m * q + m * m)
    return flops, nbytes


def backward(n: int, m: int, q: int):
    """dE = dpsi2 w psi2 (2), d(mmat) and d(zbar) through the 2q-long
    products (4q each), d(alpha) (1), per (n, i, j)."""
    return n * m * m * (8 * q + 3)
