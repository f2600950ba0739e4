"""Share of its roofline of the fused psi2 backward kernel
(``kernels/psi_stats`` ``psi2_bwd_pallas``), one call per ``chunk_size``
block of rows.  Its instruction is ``psi2_bwd.N``, which the forward's
pattern ``^psi2\\b`` does not match (``_`` is a word character).

Operations are ``bench/work/psi2.backward`` (the forward's recomputation
and the compensated arithmetic are not counted); bytes are the operands
and cotangents read once and the outputs written once, in float32.  Both
are a lower bound on what the kernel does, so the share cannot pass 100%.
At mnist-gplvm's sizes (512 x 150, q=10): 956 MFLOP (4.85 us) against
188 kB (0.23 us)."""
import re

from bench.metrics import _roofline
from bench.work import psi2

KERNEL = re.compile(r"^psi2_bwd\b")


def work(n: int, m: int, q: int, itemsize: int = 4):
    """(operations, bytes) of one call over n rows."""
    nbytes = itemsize * (n * (2 * q + 1) + m * q + q + 1  # mu s w, z, hypers
                         + m * m                          # d psi2
                         + n * (2 * q + 1) + m * q + q + 1)  # their cotangents
    return psi2.backward(n, m, q), nbytes


def read(ctx):
    c = ctx["config"]
    return _roofline.share(ctx, KERNEL, work(c["chunk_size"], c["m"], c["q"]))
