"""Device milliseconds per iteration in XLA ops of the map's forward: ops
under the ``map`` named scope (``core/stats.py``
``partial_stats_chunked``) with no ``transpose(`` before it, outside the
Pallas kernels: the block scan's glue and the stacking of the residuals
the backward reads.  Averaged over the chips."""
from bench import marks


def read(ctx):
    red = marks.of(ctx)
    t = red and red.scope_s("map")
    if not t or not ctx["iterations"]:
        return None
    return 1e3 * t / ctx["iterations"]
