"""The program's named scopes and host spans, from the program to the
readers: what the compiled programs carry, what a profiled SVI step
writes, what ``marks.load`` reads from a trace file, how ``marks.of``
finds the trace a run wrote, and the per-layer readers on a hand-made
trace and on traces recorded on the chip."""
import glob
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bench import manifest, marks, trace
from bench.tests import _tiny, _xspace
from bench.tests.test_trace import _covered

DATA = Path(__file__).parent / "data"
RECORDED = sorted(p.name for p in DATA.glob("*.json")
                  if "scopes" in p.name or "spans" in p.name)

N, Q, M, BLOCK = 512, 3, 8, 64


@pytest.fixture(scope="module")
def engine():
    import repro  # noqa: F401  (float64 on)
    from repro.core import DistributedGP

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return DistributedGP(mesh, chunk_size=BLOCK, kernel_backend="pallas")


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(7)
    rows = {"y": rng.normal(size=(N, 1)), "mu": rng.normal(size=(N, Q))}
    hyp = {"log_ell": jnp.zeros(Q), "log_sf2": jnp.zeros(()),
           "log_beta": jnp.zeros(())}
    return rows, hyp, jnp.asarray(rows["mu"][:M])


def _op_names(compiled_text: str) -> list[tuple[str, str, str]]:
    """(instruction, opcode, op_name) of every instruction with metadata."""
    return re.findall(r'^\s*(?:ROOT )?%?(\S+) = .*? ([\w-]+)\(.*?'
                      r'op_name="([^"]*)"', compiled_text, re.M)


def _check_marks(text: str) -> None:
    ops = _op_names(text)
    scopes = {marks.scope(p) for _, _, p in ops}
    assert {"map", "map_bwd", "global_step", "global_step_bwd"} <= scopes
    # the block scan: the kernel call in its body, forward and backward
    scan = [p for _, _, p in ops if "while/body/closed_call/jit(reg_stats)" in p]
    assert scan and {marks.scope(p) for p in scan} == {"map", "map_bwd"}
    assert all(marks.scope(p) == "map_bwd" for p in scan
               if "transpose(" in p)
    # Kmm's Cholesky and the triangular solves
    linalg = [p for _, _, p in ops
              if p.rsplit("/", 1)[-1] in ("cholesky", "triangular_solve")]
    assert linalg and {marks.scope(p) for p in linalg} <= {
        "global_step", "global_step_bwd"}
    # the reduce is in neither scope
    psum = [p for _, opcode, p in ops if opcode == "all-reduce"]
    assert psum and all(marks.scope(p) == "" for p in psum)


def test_exact_program_carries_the_scopes(engine, problem):
    rows, hyp, z = problem
    data, w = engine.put_data(**rows)
    step = engine.make_value_and_grad(1)
    text = step.lower(hyp, z, data["mu"], None, data["y"], w,
                      jnp.ones((1,)), jnp.asarray(float(N))).compile(
                      ).as_text()
    _check_marks(text)


def test_svi_program_carries_the_scopes(engine, problem):
    from repro.data.stream import ArraySource

    rows, hyp, z = problem
    stream = engine.put_data(stream=ArraySource(rows), blocks_per_chunk=1)
    engine.streamed_svi_value_and_grad(d=1, batch_chunks=2)
    prog = engine._stream_cache[("svi", 1, (0, 1))]
    stacked = {k: jnp.zeros((2, stream.chunk_rows) + a.shape[1:])
               for k, a in rows.items()}
    text = prog.lower(hyp, z, stacked["y"], stacked["mu"], None,
                      jnp.ones((2, stream.chunk_rows)), jnp.ones((1,)),
                      float(N), jnp.asarray(2.0)).compile().as_text()
    _check_marks(text)


@pytest.fixture(scope="module")
def svi_runner():
    from bench.traffic import svi_stream

    cell = _tiny.cell("flight-svi-stream")
    return svi_stream.Runner(cell["config"], cell["mix"], 2**31 + 5,
                             jax.devices())


def _profiled(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(files) == 1
    return out, marks.load(files[0])


def test_svi_step_writes_its_spans(tmp_path, svi_runner):
    drv = svi_runner
    _, raw = _profiled(tmp_path, drv.iteration)
    spans = raw["spans"]
    names = [s[0] for s in spans]
    assert names.count("iteration") == names.count("value_and_grad") == 1
    (vg,) = [s for s in spans if s[0] == "value_and_grad"]
    inner = sorted((s for s in spans if s[0] not in
                    ("iteration", "value_and_grad", "optimizer_update")),
                   key=lambda s: s[1])
    assert [s[0] for s in inner] == ["svi_sample"] + \
        ["chunk_assemble"] * drv.batch + ["svi_h2d", "svi_dispatch"]
    assert all(vg[1] <= s[1] and s[2] <= vg[2] for s in inner)
    # y (1 column), mu (q columns) and the weights, float64
    q = drv.rows["mu"].shape[1]
    (h2d,) = [s for s in inner if s[0] == "svi_h2d"]
    assert h2d[3] == {"bytes": drv.rows_per_iteration * (1 + q + 1) * 8}


def test_svi_step_is_bitwise_the_same_under_the_profiler(tmp_path, engine,
                                                         problem):
    from repro.data.stream import ArraySource

    rows, hyp, z = problem
    stream = engine.put_data(stream=ArraySource(rows), blocks_per_chunk=1)
    step = engine.streamed_svi_value_and_grad(d=1, batch_chunks=2)
    key = jax.random.PRNGKey(11)
    plain = step(hyp, z, stream, key)
    traced, raw = _profiled(tmp_path, lambda: step(hyp, z, stream, key))
    assert "svi_h2d" in {s[0] for s in raw["spans"]}
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        plain, traced)
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("path, where", [
    ("jit(loss)/jvp(map)/while/body/dynamic_update_slice", "map"),
    ("jit(loss)/transpose(jvp(map))/while/body/closed_call/dot_general",
     "map_bwd"),
    ("jit(neg)/transpose(jvp())/shard_map/map/while", "map_bwd"),
    ("jit(neg)/jvp()/shard_map/global_step/jit(cholesky)/cholesky",
     "global_step"),
    ("jit(loss)/transpose(jvp(global_step))/jit(cholesky)/triangular_solve",
     "global_step_bwd"),
    ("jit(neg)/transpose(jvp())/shard_map/psum", ""),
    ("jit(map)/add", ""),
    ("", ""),
])
def test_scope_of_a_path(path, where):
    """Paths as JAX writes them on the chip and on the CPU."""
    assert marks.scope(path) == where


PATHS = {"while.1": "jit(neg)/jvp(map)/while",
         "fusion.1": "jit(neg)/jvp(map)/while/body/dynamic_update_slice",
         "fusion.2": "jit(neg)/jvp()/shard_map/global_step/cholesky"}


def _write_hand_trace(f: Path, at: int = 0) -> Path:
    """A trace file written by hand, its times moved by ``at`` ns: the HLO
    proto in the metadata plane names each op's scope; the ``XLA
    Modules`` line gives its module."""
    x = _xspace
    xspace = (
        x.plane("/host:metadata", events={7: "jit_neg(7)"},
                stats={1: "Hlo Proto"},
                event_stats={7: [x.stat_bytes(1, x.hlo_proto("jit_neg",
                                                             PATHS))]})
        + x.plane("/device:TPU:0", lines=[
            x.line("XLA Modules", [(1, at + 100, 600, []),
                                   (2, at + 800, 100, [])]),
            x.line("XLA Ops", [(3, at + 100, 300, []), (4, at + 150, 50, []),
                               (5, at + 450, 100, []),
                               (4, at + 820, 30, [])])],
            events={1: "jit_neg(7)", 2: "jit_adam_update(9)",
                    3: "%while.1 = f32[] while()",
                    4: "%fusion.1 = f32[] fusion()",
                    5: "%fusion.2 = f32[] fusion()"})
        + x.plane("/host:CPU", lines=[x.line("python", [
            (1, at + 50, 1000, []), (2, at + 60, 30, [x.stat_int(2, 4)]),
            (3, at + 95, 5, [x.stat_int(1, 5120)]), (4, at + 70, 5, [])])],
            events={1: "iteration", 2: "chunk_assemble", 3: "svi_h2d",
                    4: "unread"},
            stats={1: "bytes", 2: "_ct"}))
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_bytes(xspace)
    return f


def test_load_reads_modules_scopes_and_span_arguments(tmp_path):
    paths = PATHS
    f = _write_hand_trace(tmp_path / "t.xplane.pb")
    raw = marks.load(f)
    assert raw["devices"][0] == [["while.1", 100, 400, "parent"],
                                 ["fusion.1", 150, 200, "other"],
                                 ["fusion.2", 450, 550, "other"],
                                 ["fusion.1", 820, 850, "other"]]
    where = [raw["paths"][i] for i in raw["where"][0]]
    assert where == [["jit_neg(7)", paths["while.1"]],
                     ["jit_neg(7)", paths["fusion.1"]],
                     ["jit_neg(7)", paths["fusion.2"]],
                     ["jit_adam_update(9)", ""]]
    assert raw["spans"] == [["iteration", 50, 1050],
                            ["chunk_assemble", 60, 90],
                            ["svi_h2d", 95, 100, {"bytes": 5120}]]
    red = marks.reduce(raw, 1)
    assert red.scope_s("map") == pytest.approx(300e-9)
    assert red.scope_s("global_step") == pytest.approx(100e-9)
    assert red.scope_s("") == pytest.approx(30e-9)



def _run_trace(root: Path, cell: str, at: int = 0) -> Path:
    """A hand trace where ``bench/run.py`` writes a cell's."""
    d = root / ".bench" / "trace" / cell
    _write_hand_trace(d / "plugins" / "profile" / "1" / "h.xplane.pb", at)
    return d


def test_of_finds_the_trace_the_run_wrote(tmp_path, monkeypatch):
    """The harness hands the readers ``trace.Reduced``; ``marks.of`` reads
    the newest trace file under ``.bench/trace/``, once for all readers,
    and the readers read its scopes and spans."""
    older = _run_trace(tmp_path, "other-cell", at=5000)
    f = next(older.rglob("*.xplane.pb"))
    os.utime(f, (f.stat().st_atime, f.stat().st_mtime - 60))
    red = trace.reduce_dir(_run_trace(tmp_path, "cell"), 1)
    ctx = {"trace": red, "iterations": 1, "chips": 1}
    monkeypatch.setattr(marks, "ROOT", tmp_path)
    got = marks.of(ctx)
    assert got.window == red.window
    assert marks.of(ctx) is got
    assert got.scope_s("map") == pytest.approx(300e-9)
    assert manifest.reader("global_step_ms_per_iter.exact").read(ctx) == \
        pytest.approx(100e-6)
    assert manifest.reader("stage_mb_per_s.svi").read(ctx) == \
        pytest.approx(5120 / 35e-9 / 1e6)


def test_of_reads_nothing_from_another_runs_trace(tmp_path, monkeypatch):
    """A window that is not the newest trace's, or no trace at all: the
    readers read nothing, and raise nothing."""
    red = trace.reduce_dir(_run_trace(tmp_path, "other-cell", at=5000), 1)
    ctx = {"trace": red, "iterations": 1, "chips": 1}
    monkeypatch.setattr(marks, "ROOT", tmp_path / "empty")
    assert marks.of(ctx) is None
    f = next(_run_trace(tmp_path, "cell").rglob("*.xplane.pb"))
    os.utime(f, (f.stat().st_atime, f.stat().st_mtime + 60))
    monkeypatch.setattr(marks, "ROOT", tmp_path)
    assert marks.of(ctx) is None
    for name in ("map_fwd_xla_ms_per_iter.exact", "global_step_ms_per_iter.svi",
                 "stage_idle_ms_per_step.svi", "stage_mb_per_s.svi"):
        assert manifest.reader(name).read(ctx) is None, name

# A hand-made trace of one SVI-like step, on one device.  Device 0 is busy
# [100, 770) and [800, 830) of the window [0, 1000): 700 ns, 300 idle.
HAND = {
    "devices": {"0": [
        ["while.1", 100, 400, "parent"], ["fusion.1", 100, 200, "other"],
        ["reg_stats.1", 200, 300, "kernel"], ["fusion.2", 300, 350, "other"],
        ["all-reduce.1", 400, 420, "collective"],
        ["fusion.3", 420, 520, "other"], ["fusion.4", 520, 560, "other"],
        ["while.2", 560, 760, "parent"], ["fusion.5", 560, 700, "other"],
        ["copy.1", 760, 770, "other"], ["fusion.1", 800, 830, "other"]]},
    "where": {"0": [0, 1, 2, 1, 3, 4, 5, 6, 7, 8, 9]},
    "paths": [["jit_neg(7)", "jit(neg)/jvp(map)/while"],
              ["jit_neg(7)", "jit(neg)/jvp(map)/while/body/mul"],
              ["jit_neg(7)",
               "jit(neg)/jvp(map)/while/body/closed_call/jit(reg_stats)"],
              ["jit_neg(7)", "jit(neg)/jvp()/shard_map/psum"],
              ["jit_neg(7)",
               "jit(neg)/jvp()/shard_map/global_step/jit(cholesky)/cholesky"],
              ["jit_neg(7)",
               "jit(neg)/transpose(jvp())/shard_map/global_step/div"],
              ["jit_neg(7)", "jit(neg)/transpose(jvp(map))/while"],
              ["jit_neg(7)", "jit(neg)/transpose(jvp(map))/while/body/mul"],
              ["jit_neg(7)", ""],
              ["jit_adam_update(9)", "jit(adam_update)/mul"]],
    "spans": [["iteration", 0, 1000], ["value_and_grad", 0, 780],
              ["svi_sample", 0, 40],
              ["chunk_assemble", 40, 60], ["chunk_assemble", 60, 80],
              ["svi_h2d", 80, 95, {"bytes": 5120}],
              ["svi_dispatch", 95, 110], ["optimizer_update", 780, 840]]}


@pytest.fixture
def hand():
    return marks.reduce(json.loads(json.dumps(HAND)), chips=1)


def _read(red, name, iterations=1):
    return manifest.reader(name).read({"trace": red,
                                       "iterations": iterations,
                                       "chips": len(red.devices)})


def test_readers_on_a_hand_trace(hand):
    ms = 1e-6                              # one ns, in ms
    # map forward: the loop [100, 400) less the kernel's 100 ns
    assert _read(hand, "map_fwd_xla_ms_per_iter.exact") == \
        pytest.approx(200 * ms)
    assert _read(hand, "map_bwd_ms_per_iter.exact") == pytest.approx(200 * ms)
    # 100 forward + 40 backward, over two iterations
    for cell in ("exact", "svi"):
        assert _read(hand, f"global_step_ms_per_iter.{cell}", 2) == \
            pytest.approx(70 * ms)
    # idle [0, 100): sampling 40, assembly 40 and staging 15, dispatch 5
    assert _read(hand, "sample_idle_ms_per_step.svi") == pytest.approx(40 * ms)
    assert _read(hand, "stage_idle_ms_per_step.svi") == pytest.approx(55 * ms)
    # 5120 bytes in the 55 ns of assembly and staging
    assert _read(hand, "stage_mb_per_s.svi") == pytest.approx(
        5120 / 55e-9 / 1e6)
    # the readers that were there read as before: 580 ns of XLA ops
    assert _read(hand, "xla_ms_per_iter.exact") == pytest.approx(580 * ms)
    assert hand.idle_by_span() == pytest.approx({
        "svi_sample": 40e-9, "chunk_assemble": 40e-9, "svi_h2d": 15e-9,
        "svi_dispatch": 5e-9, "value_and_grad": 10e-9,
        "optimizer_update": 30e-9, "iteration": 160e-9})
    # 580 - 200 - 200 - 140: the copy (10) and Adam's fusion (30)
    assert hand.scope_s("") == pytest.approx(40e-9)


def test_readers_without_scopes_or_spans_read_nothing():
    """A program without the marks (the parent of this change): the new
    readers return nothing, and raise nothing."""
    raw = json.loads(json.dumps(HAND))
    del raw["where"], raw["paths"]
    raw["spans"] = [s for s in raw["spans"] if s[0] in
                    ("iteration", "value_and_grad", "optimizer_update")]
    red = marks.reduce(raw, chips=1)
    for name in ("map_fwd_xla_ms_per_iter.exact", "map_bwd_ms_per_iter.exact",
                 "global_step_ms_per_iter.exact",
                 "global_step_ms_per_iter.svi", "sample_idle_ms_per_step.svi",
                 "stage_idle_ms_per_step.svi", "stage_mb_per_s.svi"):
        assert _read(red, name) is None, name


def _idle_under(raw, red, names):
    """Idle ns of device 0 whose innermost open span is in ``names``, by a
    sweep over every edge: an independent count."""
    lo, hi = red.window
    dev = min(raw["devices"], key=int)
    ops = [(max(a, lo), min(b, hi)) for _, a, b, _ in raw["devices"][dev]
           if b > lo and a < hi]
    spans = [s for s in raw["spans"] if s[2] > lo and s[1] < hi]
    edges = sorted({lo, hi} | {t for a, b in ops for t in (a, b)} |
                   {t for s in spans for t in s[1:3] if lo < t < hi})
    total = 0.0
    for a, b in zip(edges, edges[1:]):
        if any(x <= a and b <= y for x, y in ops):
            continue
        open_ = [s for s in spans if s[1] <= a and b <= s[2]]
        if open_ and min(open_, key=lambda s: s[2] - s[1])[0] in names:
            total += b - a
    return total


@pytest.mark.parametrize("name", RECORDED)
def test_new_readers_on_recorded_traces(name):
    """Traces recorded on the chip (``source`` in each file): every new
    reader equals a count made here from the raw events."""
    raw = json.loads((DATA / name).read_text())
    red = marks.reduce(raw, len(raw["devices"]))
    n = len(red.devices)
    lo, hi = red.window

    def scoped(*scopes):
        total = 0.0
        for dev, events in raw["devices"].items():
            paths = [raw["paths"][i][1] for i in raw["where"][dev]]
            mine = [(max(a, lo), min(b, hi)) for (_, a, b, _), p in
                    zip(events, paths) if marks.scope(p) in scopes
                    and b > lo and a < hi]
            work = [(max(a, lo), min(b, hi)) for _, a, b, k in events
                    if k in ("kernel", "collective") and b > lo and a < hi]
            total += _covered(mine) - (_covered(mine, work) if mine and work
                                       else 0.0)
        return total / n * 1e-6          # ms, one iteration

    expect = {"map_fwd_xla_ms_per_iter.exact": scoped("map"),
              "map_bwd_ms_per_iter.exact": scoped("map_bwd"),
              "global_step_ms_per_iter.exact":
                  scoped("global_step", "global_step_bwd")}
    # the exact cut holds the global step and the map's backward; the SVI
    # cut all three
    assert expect["map_bwd_ms_per_iter.exact"] > 0
    assert expect["global_step_ms_per_iter.exact"] > 0
    spans = [s for s in raw["spans"] if s[2] > lo and s[1] < hi]
    if any(s[0] == "svi_h2d" for s in spans):
        expect["global_step_ms_per_iter.svi"] = \
            expect["global_step_ms_per_iter.exact"]
        expect["sample_idle_ms_per_step.svi"] = \
            _idle_under(raw, red, ("svi_sample",)) * 1e-6
        expect["stage_idle_ms_per_step.svi"] = \
            _idle_under(raw, red, ("chunk_assemble", "svi_h2d")) * 1e-6
        staged = sum(s[3]["bytes"] for s in spans if s[0] == "svi_h2d")
        took = _covered([(s[1], s[2]) for s in spans
                         if s[0] in ("chunk_assemble", "svi_h2d")])
        expect["stage_mb_per_s.svi"] = staged / (took * 1e-9) / 1e6
        assert staged > 0 and expect["map_fwd_xla_ms_per_iter.exact"] > 0
    for metric, value in expect.items():
        got = _read(red, metric)
        assert (got is None) if value == 0 else \
            (got == pytest.approx(value)), metric
