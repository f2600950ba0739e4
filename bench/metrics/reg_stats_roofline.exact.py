"""Share of its roofline of the fused regression-statistics kernel
(``kernels/reg_stats``), one call per ``chunk_size`` block of rows.  At
flight-m100's sizes (2048 x 100, q=8, d=1) the least time is set by
operations (compute-bound): 45.7 MFLOP against 125 kB."""
import re

from bench.metrics import _roofline
from bench.work import reg_stats

KERNEL = re.compile(r"^reg_stats\b")


def read(ctx):
    c = ctx["config"]
    return _roofline.share(ctx, KERNEL, reg_stats.forward(
        c["chunk_size"], c["m"], c["q"], c["d"]))
