"""The psi2 backward kernel's roofline share on hand-made traces: it reads
the ``psi2_bwd.N`` calls alone, the forward's share does not count them,
and a call at the least time the chip could take reads 100% at most."""
import math

import pytest

from bench import manifest, trace
from bench.work import psi2

MNIST = {"model": "gplvm", "m": 150, "q": 10, "d": 784, "chunk_size": 512}
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _ctx(ops, config, peak):
    raw = {"devices": {"0": ops}, "spans": [["iteration", 0, 100_000]]}
    return {"trace": trace.reduce(raw, chips=1), "iterations": 1, "chips": 1,
            "config": config, "peak": peak, "rows_per_iteration": 8}


def _read(name, ctx):
    return manifest.reader(name).read(ctx)


def test_work_by_hand():
    # n=2 rows, m=3, q=1: mu s w 2 x 3, z 3, l and sf2 2; d psi2 9; the
    # cotangents of mu s w, z, l and sf2 6 + 3 + 2.
    flops, nbytes = manifest.reader("psi2_bwd_roofline.gplvm").work(2, 3, 1)
    assert flops == psi2.backward(2, 3, 1)
    assert nbytes == 4 * (6 + 3 + 2 + 9 + 6 + 3 + 2)


def test_counts_only_the_backward_kernel():
    config = {"model": "gplvm", "m": 4, "q": 1, "d": 2, "chunk_size": 8}
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    ctx = _ctx([["while.1", 0, 2000, "parent"],
                ["psi2.1", 100, 200, "kernel"],
                ["psi2_bwd.2", 300, 500, "kernel"],
                ["fusion.3", 500, 550, "other"],
                ["psi2_bwd.2", 600, 700, "kernel"],
                ["reg_stats_bwd.4", 800, 900, "kernel"]], config, peak)
    flops, nbytes = manifest.reader("psi2_bwd_roofline.gplvm").work(8, 4, 1)
    least = max(flops / 1e12, nbytes / 1e11)
    assert _read("psi2_bwd_roofline.gplvm", ctx) == pytest.approx(
        100 * 2 * least / 300e-9)
    fwd_flops, fwd_bytes = psi2.forward(8, 4, 1)
    assert _read("psi2_roofline.gplvm", ctx) == pytest.approx(
        100 * max(fwd_flops / 1e12, fwd_bytes / 1e11) / 100e-9)


def test_silent_without_the_backward_kernel():
    ctx = _ctx([["psi2.1", 100, 200, "kernel"]], MNIST, V5E)
    assert _read("psi2_bwd_roofline.gplvm", ctx) is None


def test_a_call_at_its_least_time_reads_100_at_most():
    flops, nbytes = manifest.reader("psi2_bwd_roofline.gplvm").work(
        512, 150, 10)
    least_ns = math.ceil(1e9 * max(flops / V5E["flops_per_s"],
                                   nbytes / V5E["bytes_per_s"]))
    ops = [["psi2_bwd.7", 1000 + 6000 * i, 1000 + 6000 * i + least_ns,
            "kernel"] for i in range(4)]
    share = _read("psi2_bwd_roofline.gplvm", _ctx(ops, MNIST, V5E))
    assert 99.0 < share <= 100.0
