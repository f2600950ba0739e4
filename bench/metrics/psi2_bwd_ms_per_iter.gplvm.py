"""Device milliseconds per GPLVM iteration in psi2's backward: XLA ops
under the ``psi2`` named scope with ``transpose(`` (the float64 recompute
of ``kernels/psi_stats/ops.py``'s VJP).  Averaged over the chips."""
from bench.metrics import _psi_scopes


def read(ctx):
    return _psi_scopes.ms_per_iteration(ctx, "psi2", backward=True,
                                        kernels=False)
