"""Share of the traced window in which no op ran on the device (the
union of its ops), averaged over the chips."""
from bench.metrics import _idle


def read(ctx):
    return _idle.share(ctx)
