"""Device milliseconds per exact iteration in the global step: ops under
the ``global_step`` named scope (``core/bound.py`` ``collapsed_bound``:
Kmm's Cholesky, the solves and the bound), forward and backward.
Averaged over the chips."""
from bench.metrics import _global_step


def read(ctx):
    return _global_step.ms_per_iteration(ctx)
