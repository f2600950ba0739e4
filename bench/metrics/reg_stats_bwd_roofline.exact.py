"""Share of its roofline of the fused regression-statistics backward
kernel (``kernels/reg_stats`` ``reg_stats_bwd_pallas``), one call per
``chunk_size`` block of rows.  Its instruction is ``reg_stats_bwd.N``,
which the forward's pattern ``^reg_stats\\b`` does not match.

Operations are ``bench/work/reg_stats.backward`` (the forward's
recomputation is not counted); bytes are the operands and cotangents read
once and the outputs written once, in float32.  Both are a lower bound on
what the kernel does, so the share cannot pass 100%.  At flight-m100's
sizes (2048 x 100, q=8, d=1): 51.6 MFLOP (0.26 us) against 211 kB
(0.26 us)."""
import re

from bench.metrics import _roofline
from bench.work import reg_stats

KERNEL = re.compile(r"^reg_stats_bwd\b")


def work(n: int, m: int, q: int, d: int, itemsize: int = 4):
    """(operations, bytes) of one call over n rows."""
    nbytes = itemsize * (n * (q + d + 1) + m * q + q + 1  # x y w, z, hypers
                         + m * m + m * d + 1              # dD + dD^T, dC, db
                         + m * q + m + q                  # P, s, u
                         + n * (q + d + 1))               # dx, dy, dw
    return reg_stats.backward(n, m, q, d), nbytes


def read(ctx):
    c = ctx["config"]
    return _roofline.share(ctx, KERNEL, work(c["chunk_size"], c["m"], c["q"],
                                             c["d"]))
