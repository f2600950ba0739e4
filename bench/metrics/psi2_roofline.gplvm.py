"""Share of its roofline of the fused psi2 kernel (``kernels/psi_stats``),
one call per ``chunk_size`` block of rows, against the algorithm's work
(``bench/work/psi2.forward``: every (row, a, b) term, not the compensated
arithmetic the kernel spends on each, nor the symmetry it saves), so any
later kernel is read against the same work.  At mnist-gplvm's sizes the
least time is set by operations."""
import re

from bench.metrics import _roofline
from bench.work import psi2

KERNEL = re.compile(r"^psi2\b")


def read(ctx):
    c = ctx["config"]
    return _roofline.share(ctx, KERNEL, psi2.forward(
        c["chunk_size"], c["m"], c["q"]))
