"""Idle milliseconds per SVI step of the first device while the host
samples the step's chunks (the ``svi_sample`` span: the sampler's device
program and its copy back to the host)."""
from bench.metrics import _ingest


def read(ctx):
    return _ingest.idle_ms_per_step(ctx, "svi_sample")
