"""The whole step's share of the chips' peak rate, for SVI steps."""
from bench.metrics import _mfu


def read(ctx):
    return _mfu.share(ctx)
