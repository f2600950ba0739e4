"""A kernel's share of its roofline: the least time the chip could take
for the kernel's calls in the window (the larger of operations over the
peak rate and bytes over the peak bandwidth, from ``bench/work``), over
the device time its calls took."""
from __future__ import annotations


def share(ctx: dict, pattern, work_per_call) -> float | None:
    """``pattern`` selects the kernel's ops by name among the Pallas
    kernels; ``work_per_call`` gives (operations, bytes) of one call."""
    calls, took = 0, 0.0
    for name, a, b, _ in ctx["trace"].ops("kernel"):
        if pattern.search(name):
            calls += 1
            took += (b - a) * 1e-9
    if not calls or took <= 0.0:
        return None
    flops, nbytes = work_per_call
    least = max(flops / ctx["peak"]["flops_per_s"],
                nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * calls * least / took

