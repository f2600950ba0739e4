"""Milliseconds per iteration in which a cross-chip collective (the
``lax.psum`` of ``core/distributed.py``) ran on device 0."""
from bench import trace


def read(ctx):
    red = ctx["trace"]
    dev = red.devices[min(red.devices)]
    coll = dev.by_kind.get("collective")
    if not coll or not ctx["iterations"]:
        return None
    return 1e3 * trace.length(coll) * 1e-9 / ctx["iterations"]
