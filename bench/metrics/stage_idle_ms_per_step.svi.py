"""Idle milliseconds per SVI step of the first device while the host
assembles the sampled chunks (``chunk_assemble``) and stages them on the
device (``svi_h2d``)."""
from bench.metrics import _ingest


def read(ctx):
    return _ingest.idle_ms_per_step(ctx, *_ingest.STAGE)
