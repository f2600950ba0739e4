"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler is installed here and compiles for a topology that is
described but not attached, so these tests catch what interpret mode
cannot: block shapes that are not tile-aligned, in-kernel reshapes Mosaic
cannot lower, scoped-VMEM overflows, int64 index maps, and programs the
partitioner refuses.  Nothing runs; results are covered by the
interpret-mode parity tests and by ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture (never at import), so
under pytest-xdist only the worker that runs this file loads the TPU
library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.predict import ops as predict_ops
from repro.kernels.psi_stats import ops as psi_ops
from repro.kernels.reg_stats import ops as reg_ops

# (q, d, m): the paper's flight regression and the USPS GPLVM.
WIDTHS = {"flight": (8, 1, 100), "usps": (10, 256, 150)}
ROWS = 2048          # one streaming block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip would be written to the persistent
    # cache but could not be read back without the chip.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float64):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hyp(q, sharding):
    return {"log_sf2": _sds((), sharding), "log_ell": _sds((q,), sharding),
            "log_beta": _sds((), sharding)}


def _assert_fused(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_reg_stats_compiles(one_chip, width):
    q, d, m = WIDTHS[width]

    def f(hyp, z, x, y, w):
        return reg_ops.reg_stats(hyp, z, x, y, w, interpret=False)

    _assert_fused(jax.jit(f).lower(
        _hyp(q, one_chip), _sds((m, q), one_chip), _sds((ROWS, q), one_chip),
        _sds((ROWS, d), one_chip), _sds((ROWS,), one_chip)).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_reg_stats_bwd_compiles(one_chip, width):
    """The backward kernel at the widths (m=150 takes two inducing tiles)."""
    q, d, m = WIDTHS[width]

    def f(hyp, z, x, y, w, cts):
        return reg_ops.reg_stats_bwd(hyp, z, x, y, w, cts, block_n=128,
                                     block_m=128, interpret=False)

    _assert_fused(jax.jit(f).lower(
        _hyp(q, one_chip), _sds((m, q), one_chip), _sds((ROWS, q), one_chip),
        _sds((ROWS, d), one_chip), _sds((ROWS,), one_chip),
        (_sds((), one_chip), _sds((m, d), one_chip),
         _sds((m, m), one_chip))).compile())


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_psi2_compiles(one_chip, width):
    q, _, m = WIDTHS[width]

    def f(hyp, z, mu, s, w):
        return psi_ops.psi2(hyp, z, mu, s, w, interpret=False)

    _assert_fused(jax.jit(f).lower(
        _hyp(q, one_chip), _sds((m, q), one_chip), _sds((ROWS, q), one_chip),
        _sds((ROWS, q), one_chip), _sds((ROWS,), one_chip)).compile())


# (q, m, rows a block): the USPS GPLVM's streaming block, the MNIST cell's.
PSI2_BWD = {"usps": (10, 150, ROWS), "mnist": (10, 150, 512)}


@pytest.mark.parametrize("width", sorted(PSI2_BWD))
def test_psi2_bwd_compiles(one_chip, width):
    """psi2's backward kernel at the GPLVMs' block shapes and the engine's
    tiles, as one instruction named ``psi2_bwd`` (what ``psi2_bwd_roofline.gplvm``
    reads in a profile)."""
    q, m, n = PSI2_BWD[width]

    def f(hyp, z, mu, s, w, ct):
        return psi_ops.psi2_bwd(hyp, z, mu, s, w, ct, block_n=256,
                                block_p=256, interpret=False)

    text = jax.jit(f).lower(
        _hyp(q, one_chip), _sds((m, q), one_chip), _sds((n, q), one_chip),
        _sds((n, q), one_chip), _sds((n,), one_chip),
        _sds((m, m), one_chip)).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1
    assert kernels[0].lstrip().startswith(("%psi2_bwd.", "psi2_bwd.")), \
        kernels[0][:80]


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_predict_compiles(one_chip, width):
    q, d, m = WIDTHS[width]

    def f(hyp, z, a_mean, g, x):
        return predict_ops.predict_stats(hyp, z, a_mean, g, x,
                                         interpret=False)

    _assert_fused(jax.jit(f).lower(
        _hyp(q, one_chip), _sds((m, q), one_chip), _sds((m, d), one_chip),
        _sds((m, m), one_chip), _sds((ROWS, q), one_chip)).compile())


def test_streamed_flight_svi_step_compiles(topo, monkeypatch):
    """The 1-chip streamed SVI training step at the flight width: the f64
    bound and its gradient around the fused reg_stats kernel."""
    from repro.core import DistributedGP

    # jax.default_backend() is the CPU here; steer the ops to the TPU
    # lowering as they would pick it on the chip.
    monkeypatch.setattr(reg_ops, "_on_tpu", lambda: True)
    q, d, m = WIDTHS["flight"]
    batch = 4
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    stk = NamedSharding(mesh, P(None, "data"))
    eng = DistributedGP(mesh, chunk_size=ROWS, kernel_backend="pallas")
    eng.streamed_svi_value_and_grad(d=d, batch_chunks=batch)
    prog = eng._stream_cache[("svi", d, (0, 1))]
    compiled = prog.lower(
        _hyp(q, rep), _sds((m, q), rep), _sds((batch, ROWS, d), stk),
        _sds((batch, ROWS, q), stk), None, _sds((batch, ROWS), stk),
        _sds((1,), rep), _sds((), rep), _sds((), rep)).compile()
    _assert_fused(compiled)
    # Well inside the chip's 16 GB at a 2048-row block.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_f64_cholesky_compiles_on_four_chips(topo):
    """The replicated f64 global step over a 4-chip mesh: refused by the
    TPU compiler under the Shardy partitioner (see repro/__init__.py)."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    a = _sds((128, 128), NamedSharding(mesh, P()))
    jax.jit(lambda k: jnp.linalg.cholesky(k).sum()).lower(a).compile()
