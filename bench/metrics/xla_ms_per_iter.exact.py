"""Device milliseconds per iteration in XLA ops: the map's backward
recompute (``kernels/*/ops.py`` VJPs), the global step (``core/bound.py``)
and Adam; busy time outside Pallas kernels and collectives, averaged over
the chips."""


def read(ctx):
    if not ctx["iterations"]:
        return None
    return 1e3 * ctx["trace"].kind_s("other") / ctx["iterations"]
