"""The trace reduction on a hand-made trace whose numbers are worked out
by hand, and the readers on it."""
import json
from pathlib import Path

import pytest

from bench import manifest, trace

DATA = Path(__file__).parent / "data"


@pytest.fixture
def red():
    raw = json.loads((DATA / "hand_trace.json").read_text())
    return trace.reduce(raw, chips=2)     # device 2 is not the cell's


def test_window_busy_and_idle(red):
    assert red.window == (100, 1100)
    assert sorted(red.devices) == [0, 1]
    # device 0: [100, 450] (a loop around its ops) + [500, 600] +
    # [1000, 1100]; device 1: [200, 700]
    assert trace.length(red.devices[0].busy) == 550
    assert red.busy_s == pytest.approx(525e-9)
    assert red.window_s == pytest.approx(1000e-9)
    assert trace.idle_gaps(red) == [(450, 500), (600, 1000)]


def test_kinds(red):
    assert red.kind_s("kernel") == pytest.approx((150 + 500) / 2 * 1e-9)
    # busy time in neither a kernel nor a collective: 550 - 250 and 0
    assert red.kind_s("other") == pytest.approx(300 / 2 * 1e-9)
    assert red.kind_s("collective") == pytest.approx(100 / 2 * 1e-9)


def test_readers(red):
    ctx = {"trace": red, "iterations": 2, "chips": 2,
           "config": {"model": "sgpr", "m": 4, "q": 1, "d": 1,
                      "chunk_size": 8},
           "peak": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
           "rows_per_iteration": 8}

    def read(name):
        return manifest.reader(name).read(ctx)

    assert read("device_idle.exact") == pytest.approx(47.5)
    assert read("allreduce_ms_per_iter.exact") == pytest.approx(100e-9 / 2 * 1e3)
    # 10 ns of the 100 ns all-reduce overlap fusion.2
    assert read("allreduce_exposed_ms_per_iter.exact") == pytest.approx(
        90e-9 / 2 * 1e3)
    assert read("xla_ms_per_iter.exact") == pytest.approx(150e-9 / 2 * 1e3)
    from bench.work import reg_stats
    flops, nbytes = reg_stats.forward(8, 4, 1, 1)
    least = max(flops / 1e12, nbytes / 1e11)
    assert read("reg_stats_roofline.exact") == pytest.approx(
        100 * 2 * least / 650e-9)
    assert read("psi2_roofline.exact") is None     # no psi2 kernel here


def test_breakdown_labels_gaps_by_span(red):
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["reg_stats.1", pytest.approx(650e-9)]
    assert b["idle_gaps"] == [["iteration", pytest.approx(400e-9)],
                              ["value_and_grad", pytest.approx(50e-9)]]


def test_union_and_intersect():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.intersect([(0, 2), (3, 6)], [(1, 4)]) == [(1, 2), (3, 4)]


def test_names_kinds_and_loops():
    text = ('%reg_stats.3 = (f32[1,1]{1,0}) custom-call(%a), '
            'custom_call_target="tpu_custom_call", backend_config={}')
    assert trace.op_name(text) == "reg_stats.3"
    assert trace.op_kind(text, {}) == "kernel"
    x64 = '%custom-call.9 = f64[] custom-call(%b), custom_call_target="X64Combine"'
    assert trace.op_kind(x64, {}) == "other"
    assert trace.op_kind("%all-reduce.2 = f64[4] all-reduce(%c)", {}) == \
        "collective"
    assert trace.op_kind("%fusion.4 = f64[4] fusion(%all-reduce.2)", {}) == \
        "other"
    ops = [["b", 20, 30, "other"], ["loop", 10, 90, "other"],
           ["c", 40, 90, "other"], ["d", 95, 99, "other"]]
    trace._mark_parents(ops)
    assert [o[3] for o in ops] == ["parent", "other", "other", "other"]


def _covered(intervals, also=None):
    """Length covered by ``intervals`` (and, if given, by ``also`` at the
    same time), by a sweep over their edges: a count, not a merge."""
    edges = [(a, 1, 0) for a, b in intervals] + [(b, -1, 0) for a, b in intervals]
    edges += [(a, 0, 1) for a, b in also or []] + [(b, 0, -1) for a, b in also or []]
    n = m = 0
    last = total = 0.0
    for t, dn, dm in sorted(edges):
        if n > 0 and (also is None or m > 0):
            total += t - last
        n, m, last = n + dn, m + dm, t
    return total


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")
                                        if p.name != "hand_trace.json"))
def test_recorded_trace_against_a_sweep(name):
    """Traces recorded on the chip (``source`` in each file): the merged
    busy, kernel and collective times and the exposed collective time
    equal a sweep over the raw events."""
    raw = json.loads((DATA / name).read_text())
    chips = len(raw["devices"])
    red = trace.reduce(raw, chips)
    lo, hi = red.window
    for dev_id, dev in red.devices.items():
        ops = [(max(a, lo), min(b, hi), k) for _, a, b, k in
               raw["devices"][str(dev_id)] if b > lo and a < hi]
        assert trace.length(dev.busy) == pytest.approx(
            _covered([(a, b) for a, b, _ in ops]))
        for kind in ("kernel", "collective"):
            assert trace.length(dev.by_kind.get(kind, [])) == pytest.approx(
                _covered([(a, b) for a, b, k in ops if k == kind]))
        coll = [(a, b) for a, b, k in ops if k == "collective"]
        work = [(a, b) for a, b, k in ops if k in ("kernel", "other")]
        hidden = trace.length(trace.intersect(dev.by_kind.get(
            "collective", []), dev.work(("kernel", "other"))))
        assert hidden == pytest.approx(_covered(coll, work) if coll else 0.0)
    idle = 100 * (1 - red.busy_s / red.window_s)
    assert 0.0 <= idle < 100.0
