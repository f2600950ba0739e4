"""Device milliseconds per GPLVM iteration in psi2's forward: ops under
the ``psi2`` named scope without ``transpose(``, the fused kernel
(``kernels/psi_stats``) included.  Averaged over the chips."""
from bench.metrics import _psi_scopes


def read(ctx):
    return _psi_scopes.ms_per_iteration(ctx, "psi2", backward=False,
                                        kernels=True)
