"""The host's staging rate in MB/s: the bytes the ``svi_h2d`` spans
staged (their ``bytes`` argument) over the time in ``chunk_assemble`` or
``svi_h2d``, the whole of the SVI step's host ingestion."""
from bench import marks, trace
from bench.metrics import _ingest


def read(ctx):
    red = marks.of(ctx)
    if red is None:
        return None
    staged = sum(s[3].get("bytes", 0) for s in red.spans
                 if s[0] == "svi_h2d" and len(s) > 3)
    took = trace.length(red.span_union(*_ingest.STAGE)) * 1e-9
    if not staged or took <= 0.0:
        return None
    return staged / took / 1e6
