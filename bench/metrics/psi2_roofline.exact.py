"""Share of its roofline of the fused psi2 kernel (``kernels/psi_stats``),
one call per ``chunk_size`` block of rows.  At usps-gplvm's sizes (512
rows, m=150, q=10) the least time is set by operations (compute-bound)."""
import re

from bench.metrics import _roofline
from bench.work import psi2

KERNEL = re.compile(r"^psi2\b")


def read(ctx):
    c = ctx["config"]
    return _roofline.share(ctx, KERNEL, psi2.forward(
        c["chunk_size"], c["m"], c["q"]))
