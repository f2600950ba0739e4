"""Milliseconds per iteration in which a collective ran on device 0 and
no other op did (enclosing loops aside): the part of the reduce that
nothing hides."""
from bench import trace


def read(ctx):
    red = ctx["trace"]
    dev = red.devices[min(red.devices)]
    coll = dev.by_kind.get("collective")
    if not coll or not ctx["iterations"]:
        return None
    compute = dev.work(("kernel", "other"))
    exposed = trace.length(coll) - trace.length(trace.intersect(coll, compute))
    return 1e3 * exposed * 1e-9 / ctx["iterations"]
