"""The SVI step's host spans (``core/distributed.py``
``streamed_svi_value_and_grad``, ``data/stream.py`` ``BlockStream.chunk``)
against the first device's idle time."""
from bench import marks

STAGE = ("chunk_assemble", "svi_h2d")


def idle_ms_per_step(ctx, *spans):
    """Idle milliseconds per step put down to ``spans`` (their self
    time), or None where the trace has none of them."""
    red = marks.of(ctx)
    if red is None or not ctx["iterations"] or not red.span_union(*spans):
        return None
    idle = red.idle_by_span()
    return 1e3 * sum(idle.get(s, 0.0) for s in spans) / ctx["iterations"]
