"""The mnist-gplvm-exact cell: its generator, its per-layer readers on a
hand-made trace, and whole tiny runs on the CPU, correct when sound and
not correct with each fault of a training cell planted in the timed path
or in the reference."""
import json

import numpy as np
import pytest

from bench import compare, control, manifest, marks
from bench.gen import mnist
from bench.tests import _tiny
from bench.work import iteration, psi2

CELL = "mnist-gplvm-exact"


def test_mnist_images_shape_range_and_seed():
    y, labels = mnist.mnist_images(np.random.default_rng(2**31 + 7), n=300)
    assert y.shape == (300, 784) and labels.shape == (300,)
    assert y.min() == 0.0 and y.max() <= 1.0 and y.max() > 0.9
    assert set(labels) <= set(range(10))
    again, _ = mnist.mnist_images(np.random.default_rng(2**31 + 7), n=300)
    np.testing.assert_array_equal(y, again)
    other, _ = mnist.mnist_images(np.random.default_rng(2**31 + 8), n=300)
    assert not np.array_equal(y, other)


def test_kmeans_matches_the_usps_start_without_the_n_m_q_temporary():
    from bench.gen.usps import kmeans as usps_kmeans

    x = np.random.default_rng(3).standard_normal((400, 10))
    np.testing.assert_allclose(mnist.kmeans(x, 20, iters=5, seed=9),
                               usps_kmeans(x, 20, iters=5, seed=9),
                               rtol=1e-12, atol=1e-12)


def test_gplvm_start_is_the_configured_start():
    y, mu, s, hyp, z = mnist.gplvm_start(300, 10, 16, 2**31 + 1)
    assert mu.shape == (300, 10) and z.shape == (16, 10)
    np.testing.assert_allclose(mu.std(axis=0), 1.0, rtol=1e-12)
    assert np.all(s == 0.5) and np.all(hyp["log_ell"] == 0.0)
    np.testing.assert_allclose(hyp["log_sf2"], np.log(np.var(y)))
    np.testing.assert_allclose(hyp["log_beta"], -np.log(0.01 * np.var(y)))


# One GPLVM iteration on one device, window [0, 1000) ns: the map's loop
# (psi1_c 50 ns, psi2's XLA 50 and its kernel 200, 50 hoisted out of the
# loop), the global step 50, the backward's loop (psi2 200, psi1_c 60),
# Adam 100; idle [0, 100) and [900, 1000).
FWD = "jit(neg)/jvp()/shard_map/map/while/body/closed_call"
BWD = "jit(neg)/transpose(jvp())/shard_map/map/while/body/closed_call"
HAND = {
    "devices": {"0": [
        ["while.1", 100, 400, "parent"], ["fusion.1", 100, 150, "other"],
        ["fusion.2", 150, 200, "other"], ["psi2.3", 200, 400, "kernel"],
        ["fusion.4", 400, 450, "other"], ["fusion.5", 450, 500, "other"],
        ["while.2", 500, 800, "parent"], ["fusion.6", 500, 700, "other"],
        ["fusion.7", 700, 760, "other"], ["fusion.8", 800, 900, "other"]]},
    "where": {"0": list(range(10))},
    "paths": [["jit_neg(1)", "jit(neg)/jvp()/shard_map/map/while"],
              ["jit_neg(1)", f"{FWD}/psi1_c/dot_general"],
              ["jit_neg(1)", f"{FWD}/psi2/jit(psi2)/concatenate"],
              ["jit_neg(1)", f"{FWD}/psi2/jit(psi2)/pallas_call"],
              ["jit_neg(1)", "jit(neg)/jvp()/shard_map/psi2/jit(psi2)/exp"],
              ["jit_neg(1)",
               "jit(neg)/jvp()/shard_map/global_step/jit(cholesky)/cholesky"],
              ["jit_neg(1)", "jit(neg)/transpose(jvp())/shard_map/map/while"],
              ["jit_neg(1)", f"{BWD}/psi2/jit(psi2)/jvp()/exp"],
              ["jit_neg(1)", f"{BWD}/psi1_c/dot_general"],
              ["jit_adam_update(2)", "jit(adam_update)/mul"]],
    "spans": [["iteration", 0, 1000], ["value_and_grad", 0, 800],
              ["optimizer_update", 800, 900]]}
CONFIG = {"model": "gplvm", "n": 512, "m": 150, "q": 10, "d": 784,
          "chunk_size": 512}


def _ctx(raw):
    return {"trace": marks.reduce(json.loads(json.dumps(raw)), chips=1),
            "iterations": 1, "chips": 1, "config": CONFIG,
            "peak": _tiny.PEAK, "rows_per_iteration": CONFIG["n"]}


def _cell_readers():
    c = manifest.cell(manifest.load(), CELL)
    return {m["name"] for m in c["per_layer"]}


def test_readers_on_a_hand_trace():
    ctx, ms = _ctx(HAND), 1e-6                # one ns, in ms
    read = {name: manifest.reader(name).read(ctx) for name in
            _cell_readers()}
    assert read["psi2_fwd_ms_per_iter.gplvm"] == pytest.approx(300 * ms)
    assert read["psi2_bwd_ms_per_iter.gplvm"] == pytest.approx(200 * ms)
    assert read["psi1_c_ms_per_iter.gplvm"] == pytest.approx(110 * ms)
    # The accepted readers of the exact cells: the backward's loop
    # [500, 800) (both scopes and the loop's own ops), the busy time
    # outside the kernel, the global step, the idle share.
    assert read["map_bwd_ms_per_iter.exact"] == pytest.approx(300 * ms)
    assert read["xla_ms_per_iter.exact"] == pytest.approx(600 * ms)
    assert read["global_step_ms_per_iter.exact"] == pytest.approx(50 * ms)
    assert read["device_idle.exact"] == pytest.approx(20.0)
    flops, nbytes = psi2.forward(512, 150, 10)
    least = max(flops / _tiny.PEAK["flops_per_s"],
                nbytes / _tiny.PEAK["bytes_per_s"])
    assert read["psi2_roofline.gplvm"] == pytest.approx(
        100 * least / 200e-9)
    assert read["mfu.exact"] == pytest.approx(
        100 * iteration.flops(CONFIG, 512) / 1e-6 /
        _tiny.PEAK["flops_per_s"])


def test_readers_without_the_scopes_read_nothing():
    """The parent's program has no psi2 or psi1_c scope: those readers
    return nothing and raise nothing; without paths at all, neither does
    the global step's."""
    raw = json.loads(json.dumps(HAND))
    raw["paths"] = [[mod, p.replace("/psi2", "").replace("/psi1_c", "")]
                    for mod, p in raw["paths"]]
    ctx = _ctx(raw)
    for name in ("psi2_fwd_ms_per_iter.gplvm", "psi2_bwd_ms_per_iter.gplvm",
                 "psi1_c_ms_per_iter.gplvm"):
        assert manifest.reader(name).read(ctx) is None, name
    del raw["where"], raw["paths"]
    ctx = _ctx(raw)
    for name in ("psi2_fwd_ms_per_iter.gplvm", "psi1_c_ms_per_iter.gplvm",
                 "global_step_ms_per_iter.exact"):
        assert manifest.reader(name).read(ctx) is None, name


def test_scope_is_a_whole_path_component():
    """A scope is a component of the path, also inside a transformation's
    mark, and never the name of a ``jit``."""
    from bench.metrics import _psi_scopes

    assert _psi_scopes._in(f"{FWD}/psi2/jit(psi2)/pallas_call", "psi2")
    assert _psi_scopes._in("jit(neg)/jvp(psi2)/exp", "psi2")
    assert not _psi_scopes._in(f"{FWD}/psi2/jit(psi2)/exp", "psi1_c")
    assert not _psi_scopes._in("jit(neg)/jvp()/jit(psi2)/exp", "psi2")


def test_sound_run_is_correct():
    out = _tiny.run(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(monkeypatch, fault):
    _tiny.plant(monkeypatch, fault)
    out = _tiny.run(CELL)
    assert not out["correct"], (fault, out["checks"])


def test_control_and_faults():
    c = _tiny.cell(CELL)
    r = control.readings_for_seed(c, 2**31 + 99, True)

    def correct(kind):
        return compare.judge(r[kind], c["limits"])[0]

    assert correct("program"), r["program"]
    assert max(r["control_f32"][k] / max(r["program"][k], 1e-300)
               for k in compare.NAMES) > 5.0, (r["control_f32"], r["program"])
    for kind in ("unchanged", "half_batch", "altered"):
        assert not correct(kind), (kind, r[kind])
