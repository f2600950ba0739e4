"""Device milliseconds per SVI step in the global step: ops under the
``global_step`` named scope (``core/bound.py`` ``collapsed_bound``:
Kmm's Cholesky, the solves and the bound), forward and backward."""
from bench.metrics import _global_step


def read(ctx):
    return _global_step.ms_per_iteration(ctx)
