"""The backward kernel's roofline share on hand-made traces: it reads the
``reg_stats_bwd.N`` calls alone, the forward's share does not count them,
and a call at the least time the chip could take reads 100% at most."""
import math

import pytest

from bench import manifest, trace
from bench.work import reg_stats

FLIGHT = {"model": "sgpr", "m": 100, "q": 8, "d": 1, "chunk_size": 2048}
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _ctx(ops, config, peak):
    raw = {"devices": {"0": ops}, "spans": [["iteration", 0, 10_000]]}
    return {"trace": trace.reduce(raw, chips=1), "iterations": 1, "chips": 1,
            "config": config, "peak": peak, "rows_per_iteration": 8}


def _read(name, ctx):
    return manifest.reader(name).read(ctx)


def test_work_by_hand():
    # n=2 rows, m=3, q=1, d=1: x y w 2 x 3, z 3, l and sf2 2; dD + dD^T 9,
    # dC 3, db 1; P 3, s 3, u 1; dx dy dw 2 x 3.
    flops, nbytes = manifest.reader("reg_stats_bwd_roofline.exact").work(
        2, 3, 1, 1)
    assert flops == reg_stats.backward(2, 3, 1, 1)
    assert nbytes == 4 * (6 + 3 + 2 + 9 + 3 + 1 + 3 + 3 + 1 + 6)


def test_counts_only_the_backward_kernel():
    config = {"model": "sgpr", "m": 4, "q": 1, "d": 1, "chunk_size": 8}
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    ctx = _ctx([["while.1", 0, 2000, "parent"],
                ["reg_stats.1", 100, 200, "kernel"],
                ["reg_stats_bwd.2", 300, 500, "kernel"],
                ["fusion.3", 500, 550, "other"],
                ["reg_stats_bwd.2", 600, 700, "kernel"],
                ["psi2.4", 800, 900, "kernel"]], config, peak)
    flops, nbytes = manifest.reader("reg_stats_bwd_roofline.exact").work(
        8, 4, 1, 1)
    least = max(flops / 1e12, nbytes / 1e11)
    assert _read("reg_stats_bwd_roofline.exact", ctx) == pytest.approx(
        100 * 2 * least / 300e-9)
    fwd_flops, fwd_bytes = reg_stats.forward(8, 4, 1, 1)
    assert _read("reg_stats_roofline.exact", ctx) == pytest.approx(
        100 * max(fwd_flops / 1e12, fwd_bytes / 1e11) / 100e-9)


def test_silent_without_the_backward_kernel():
    ctx = _ctx([["reg_stats.1", 100, 200, "kernel"]], FLIGHT, V5E)
    assert _read("reg_stats_bwd_roofline.exact", ctx) is None


def test_a_call_at_its_least_time_reads_100_at_most():
    flops, nbytes = manifest.reader("reg_stats_bwd_roofline.exact").work(
        2048, 100, 8, 1)
    least_ns = math.ceil(1e9 * max(flops / V5E["flops_per_s"],
                                   nbytes / V5E["bytes_per_s"]))
    ops = [["reg_stats_bwd.7", 1000 + 400 * i, 1000 + 400 * i + least_ns,
            "kernel"] for i in range(4)]
    share = _read("reg_stats_bwd_roofline.exact", _ctx(ops, FLIGHT, V5E))
    assert 99.0 < share <= 100.0
