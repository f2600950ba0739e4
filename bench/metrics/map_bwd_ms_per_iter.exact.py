"""Device milliseconds per iteration in the map's backward: ops under the
``map`` named scope with a ``transpose(`` before it (the transposed block
scan, and in it the kernels' float64 VJPs, ``kernels/*/ops.py``).
Averaged over the chips."""
from bench import marks


def read(ctx):
    red = marks.of(ctx)
    t = red and red.scope_s("map_bwd")
    if not t or not ctx["iterations"]:
        return None
    return 1e3 * t / ctx["iterations"]
