"""Device milliseconds per GPLVM iteration in psi1, C = psi1^T Y and
psi0: XLA ops under the ``psi1_c`` named scope, forward and backward.
Averaged over the chips."""
from bench.metrics import _psi_scopes


def read(ctx):
    return _psi_scopes.ms_per_iteration(ctx, "psi1_c", backward=None,
                                        kernels=False)
