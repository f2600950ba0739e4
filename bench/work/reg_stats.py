"""Operations and bytes of the regression map statistics, from the
algorithm's shapes.

For a block of n rows, m inducing inputs, q input and d output columns:
K = sf2 exp(-1/2 |x - z|^2 / l2) with the squared distance as one
(n, q) x (q, m) product plus O(nm) terms; C = K^T (w y); D = (w K)^T K;
b = sum w sf2.  Every multiply and add counts one operation, and each exp
one.  Bytes are the operands read once and the outputs written once, in
the configuration's map dtype (float32: 4 bytes).
"""
from __future__ import annotations


def forward(n: int, m: int, q: int, d: int, itemsize: int = 4):
    """(operations, bytes) of one call over n rows."""
    flops = (2 * n * m * q        # cross term of the squared distance
             + 4 * n * m          # norms, scale, exp argument, sf2 *
             + n * m              # exp
             + 2 * n * m * d      # C
             + n * m + 2 * n * m * m   # w K, then D
             + 2 * n)             # b
    nbytes = itemsize * (n * (q + d + 1) + m * q + m * d + m * m + q + 2)
    return flops, nbytes


def backward(n: int, m: int, q: int, d: int):
    """Operations of the gradient with respect to (hyp, z), not counting
    the forward recomputation: dK from dC (2nmd) and dD (2nm^2 for the
    symmetrised product), the chain through exp (2nm), and dz, dl from the
    distance (6nmq)."""
    return 2 * n * m * d + 2 * n * m * m + 2 * n * m + 6 * n * m * q
