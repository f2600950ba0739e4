"""Bring-up check of the main path on a TPU, at the paper's full width.

Drives the entry points a user calls — ``DistributedGP`` (streamed SGPR
training and the GPLVM), ``PredictEngine`` and ``Frontend`` — with the
fused Pallas kernels (``kernel_backend="pallas"``), and checks what comes
out against the XLA path and a float64 reference.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the multi-chip phase only

One chip:
  flight   paper §5 / ROADMAP W1: ``synthetic.flight_like`` n=2,000,000,
           q=8, d=1, m=100, SE-ARD, streamed in 2048-row blocks — a few
           ``streamed_svi_value_and_grad`` Adam steps, one
           ``streamed_bound`` over all rows (fused and XLA), and
           ``streamed_predictive_state``.
  serving  ``PredictEngine(kernel_backend="pallas")`` on 4096-row query
           batches against the XLA engine, then concurrent ``Frontend``
           requests.
  gplvm    ``gplvm-usps`` (n=4649, d=256, q=10, m=150) with the fused
           psi2: the bound fused against XLA, at the start and at
           ``default_hyp`` (where a float32 D makes Bmat indefinite);
           value and gradients fused against XLA on a 512-row slice;
           value-and-grad steps at full size.
  reference  the fused bound on a 20,000-row slice against
           ``ref_naive.titsias_bound_direct`` in float64 on the CPU.
Four chips (``--chips 4``): the flight exact bound and gradients, and one
SVI step, on a 4-chip data mesh under ``reduce_mode="serial"`` and
``"overlap"``, each against the same computation on device 0 alone; plus
the streamed exact bound, 4 chips against device 0.

Every check prints its error beside its limit.  The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every phase
and check passed.  Exit status: 0 passed, 1 a phase or check failed, 2 no
TPU (there is no CPU fallback and no interpret mode here) or the ``repro``
package is not next to this script.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# -- tolerances ---------------------------------------------------------------
# On the TPU the fused kernels compute their tiles in float32 (MXU-native;
# f64 is emulated), while the XLA path and the bound's m x m global step
# run in float64.  Each statistic is an f32 sum over one 2048-row block
# (relative error ~1e-6); blocks are folded in f64.  The bound's data-fit
# and trace terms are O(n) and partly cancel, which amplifies that error
# by up to ~100x, so float32 statistics give a bound good to ~1e-5..1e-4
# relative.  1e-4 is the limit the repo's benchmarks already hold the TPU
# to against f64 (benchmarks/streaming.py); a bug (a dropped block, a
# wrong padding weight, a misplaced tile) moves the bound by far more.
BOUND_RTOL = 1e-4
# The USPS GPLVM: the fused psi2 sums D to ~1e-13 per entry (compensated
# f32, kernels/psi_stats), psi1 and C are f64.  Rounding the f64
# statistics of this phase's start point to f32 would move its bound by
# 1.2e-6 relative (CPU, float64 reference); 1e-4 is the flight's limit.
GPLVM_RTOL = 1e-4
# The fused path's backward is the float64 XLA recompute
# (kernels/psi_stats/ops.py), so the gradients' only reduced-precision
# input is the forward D, through the bound's cotangent.  Where Kmm is
# ill-conditioned a float32 D alone moves the gradients far more than the
# bound: on the USPS 512-row slice, rounding the exact D to float32 moves
# the worst leaf (z) by 6.0e-3 of its largest entry and the bound by
# 1.3e-6 (CPU, float64).  The fused gradients are held to a multiple of
# that floor, measured in the same run (the float32 kernel measured 4.9x
# it on the chip); a wrong cotangent, weight or transpose moves them by
# O(1).
GPLVM_GRAD_FACTOR = 10.0
# Served mean / variance: the predict kernel runs in f32 against the f64
# XLA engine.  The variance is k** - quad with quad close to k** when the
# posterior is tight, so its error is measured against the prior variance
# sf2 (the scale of both terms), the mean's against max(1, max |mean|).
# f32 tiles give ~1e-6 on both (the kernel in f32 interpret mode on the
# CPU, 200k-row flight state); tiles rounded to bf16 would give ~1e-3.
SERVE_MEAN_TOL = 1e-4
SERVE_VAR_TOL = 1e-4
# Four chips against device 0.  Each shard holds whole 2048-row blocks, so
# both sides sum the same f32 block statistics, and on the CPU (IEEE f64)
# they agree to 1e-16 on the bound and 2e-15 on the gradients.  The first
# four-chip run measured 8.1e-8 on the bound and 9.8e-6 on the gradients:
# the fused regression kernel handed back f32 statistics, so the blocks
# were folded in f32, and 245 blocks per shard summed differently from 977
# on one device (1e-6 of D; PERF.md).  The kernel's outputs are now cast
# to float64 before the fold, and a four-chip probe then found the reduced
# C and D of 4 chips and device 0 equal bit for bit.  What is left is the
# emulated-f64 global step compiled into two programs (one program on the
# chip against the CPU, same Stats: 1.8e-8 on the bound, 3.1e-8 on the
# gradients).  The limits were set from the first run with ~10x room and
# are kept until this phase is run again on four chips: a lost or
# doubled shard moves the bound by ~25%, a reduce in bf16 by ~1e-3.
MESH_BOUND_RTOL = 1e-6
MESH_GRAD_RTOL = 1e-4

FLIGHT = dict(n=2_000_000, m=100, chunk=2048, batch_chunks=4, steps=4)
SERVE = dict(rows=4096, batches=4, requests=8, request_rows=256)
REF_ROWS = 20_000


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects every comparison; a phase that raises counts as failed."""

    def __init__(self):
        self.failed: list[str] = []

    def within(self, name: str, err: float, limit: float) -> None:
        ok = bool(err <= limit)          # NaN fails
        log(f"check {name}: err={err!r} limit={limit!r} "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def true(self, name: str, cond: bool, detail: str = "") -> None:
        log(f"check {name}: {detail} {'ok' if cond else 'FAIL'}")
        if not cond:
            self.failed.append(name)

    def phase(self, name: str, fn, *args, **kwargs):
        log(f"== phase {name} ==")
        t0 = time.perf_counter()
        try:
            out = fn(self, *args, **kwargs)
        except Exception:                    # noqa: BLE001 — report, go on
            traceback.print_exc()
            self.failed.append(f"phase {name}")
            log(f"phase {name}: FAIL")
            return None
        log(f"phase {name}: {time.perf_counter() - t0:.2f} s, "
            f"peak_bytes_in_use={_peak_bytes()}")
        return out


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def timed(fn, *args, **kwargs):
    """(result, seconds) around ``fn`` ending in ``block_until_ready``."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def rel_err(a, b) -> float:
    return abs(float(a) - float(b)) / abs(float(b))


def has_kernel(compiled_text: str) -> bool:
    return "tpu_custom_call" in compiled_text


def adam(params, grads, state, t, lr):
    import jax
    import jax.numpy as jnp
    b1, b2, eps = 0.9, 0.999, 1e-8
    mom, vel = state
    mom = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mom, grads)
    vel = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, vel, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mom, vel)
    return params, (mom, vel)


def all_finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(tree))


def flight_init(src, m: int):
    """Inducing inputs from the first rows' covariates; unit hypers."""
    import jax.numpy as jnp
    import numpy as np
    first = src.read(0, max(4 * m, 4096))
    rng = np.random.default_rng(0)
    z = jnp.asarray(first["mu"][rng.choice(first["mu"].shape[0], m,
                                           replace=False)])
    hyp = {"log_sf2": jnp.asarray(0.0), "log_ell": jnp.zeros(8),
           "log_beta": jnp.asarray(1.0)}
    return hyp, z


# -- one chip -----------------------------------------------------------------

def phase_flight(ck: Checks, mesh, n, m, chunk, batch_chunks, steps):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import DistributedGP
    from repro.data.synthetic import flight_like

    src = flight_like(n=n, seed=0)
    eng = DistributedGP(mesh, chunk_size=chunk, kernel_backend="pallas")
    stream = eng.put_data(stream=src)
    log(f"flight_like n={n} q=8 d=1 m={m}: {stream.n_chunks} chunks of "
        f"{stream.chunk_rows} rows")
    hyp, z = flight_init(src, m)

    step = eng.streamed_svi_value_and_grad(d=1, batch_chunks=batch_chunks)
    params = (hyp, z)
    opt = (jax.tree.map(jnp.zeros_like, params),) * 2
    key = jax.random.PRNGKey(1)
    times = []
    for i in range(steps):
        key, sub = jax.random.split(key)
        (v, grads), dt = timed(step, params[0], params[1], stream, sub)
        times.append(dt)
        ck.true(f"flight/svi_step{i}_finite", all_finite((v, grads)),
                f"stochastic bound={-float(v)!r}")
        params, opt = adam(params, grads, opt, i + 1, lr=2e-2)
    log(f"flight/svi_step: first call {times[0]:.3f} s (compile + run), "
        f"steady {min(times[1:]):.4f} s over {steps - 1} steps "
        f"({batch_chunks * stream.chunk_rows} rows/step)")
    hyp, z = params

    # The compiled SVI step must hold the fused kernel, not an XLA map.
    rows = stream.chunk_rows
    stk = NamedSharding(mesh, P(None, eng.data_axes))
    rep = NamedSharding(mesh, P())

    def sds(shape, sh):
        return jax.ShapeDtypeStruct(shape, jnp.float64, sharding=sh)

    prog = eng._stream_cache[("svi", 1, (0, 1))]   # the step's own program
    text = prog.lower(
        jax.tree.map(lambda a: sds(a.shape, rep), hyp), sds(z.shape, rep),
        sds((batch_chunks, rows, 1), stk), sds((batch_chunks, rows, 8), stk),
        None, sds((batch_chunks, rows), stk), sds((eng.n_shards,), rep),
        sds((), rep), sds((), rep)).compile().as_text()
    ck.true("flight/svi_step_has_tpu_custom_call", has_kernel(text))

    bound, dt1 = timed(eng.streamed_bound, hyp, z, stream, d=1)
    _, dt2 = timed(eng.streamed_bound, hyp, z, stream, d=1)
    log(f"flight/streamed_bound (pallas) over {n} rows: {float(bound)!r}; "
        f"first {dt1:.3f} s, second {dt2:.3f} s")
    eng_x = DistributedGP(mesh, chunk_size=chunk, kernel_backend="xla")
    bound_x, dtx = timed(eng_x.streamed_bound, hyp, z,
                         eng_x.put_data(stream=src), d=1)
    log(f"flight/streamed_bound (xla): {float(bound_x)!r}; {dtx:.3f} s")
    ck.within("flight/bound_fused_vs_xla_rel", rel_err(bound, bound_x),
              BOUND_RTOL)

    state, dt = timed(eng.streamed_predictive_state, hyp, z, stream)
    log(f"flight/streamed_predictive_state: {dt:.3f} s")
    ck.true("flight/state_finite", all_finite(state))
    return hyp, z, state


def phase_serving(ck: Checks, state, rows, batches, requests, request_rows):
    import numpy as np

    from repro.data.synthetic import flight_like
    from repro.serve.engine import PredictEngine
    from repro.serve.frontend import Frontend

    eng = PredictEngine(state, kernel_backend="pallas")
    ref = PredictEngine(state, kernel_backend="xla")
    src = flight_like(n=rows * batches, seed=99)
    sf2 = float(np.exp(np.asarray(state.hyp["log_sf2"])))

    def errors(x, mean, var):
        """(mean error / max(1, max|mean|), variance error / sf2)."""
        mean_r, var_r = (np.asarray(a) for a in ref.predict(x))
        scale = max(1.0, float(np.max(np.abs(mean_r))))
        return (float(np.max(np.abs(np.asarray(mean) - mean_r))) / scale,
                float(np.max(np.abs(np.asarray(var) - var_r))) / sf2)

    times, errs = [], []
    for b in range(batches):
        x = src.read(b * rows, (b + 1) * rows)["mu"]
        (mean, var), dt = timed(eng.predict, x)
        times.append(dt)
        errs.append(errors(x, mean, var))
    log(f"serving/predict {rows} rows: first {times[0]:.4f} s, steady "
        f"{min(times[1:]):.5f} s ({rows / min(times[1:]):.0f} rows/s)")
    ck.within("serving/mean_vs_xla", max(e[0] for e in errs), SERVE_MEAN_TOL)
    ck.within("serving/var_vs_xla_over_sf2", max(e[1] for e in errs),
              SERVE_VAR_TOL)

    xq, _ = eng.pad_queries(src.read(0, rows)["mu"])
    text = eng._run.lower(eng.compute_state, xq).compile().as_text()
    ck.true("serving/predict_has_tpu_custom_call", has_kernel(text))

    reqs = [src.read(i * request_rows, (i + 1) * request_rows)["mu"]
            for i in range(requests)]

    async def drive():
        fe = Frontend(eng, max_batch_rows=4 * request_rows, max_wait_ms=2.0)
        fe.warmup()
        async with fe:
            t0 = time.perf_counter()
            out = await asyncio.gather(*(fe.submit(x) for x in reqs))
            return out, time.perf_counter() - t0

    results, dt = asyncio.run(drive())
    log(f"serving/frontend: {requests} concurrent requests of "
        f"{request_rows} rows answered in {dt:.4f} s")
    errs = [errors(x, r.mean, r.var) for x, r in zip(reqs, results)]
    ck.within("serving/frontend_mean_vs_xla", max(e[0] for e in errs),
              SERVE_MEAN_TOL)
    ck.within("serving/frontend_var_vs_xla_over_sf2",
              max(e[1] for e in errs), SERVE_VAR_TOL)


def phase_gplvm(ck: Checks, mesh, n, d, q, m, chunk, steps, slice_rows):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedGP
    from repro.core.covariance import as_kernel
    from repro.core.init_utils import default_hyp, kmeans, pca
    from repro.data.synthetic import usps_like

    rng = np.random.default_rng(0)
    y, _ = usps_like(rng, n=n)
    mu0 = pca(y, q)
    hyp0 = {k: jnp.asarray(v) for k, v in default_hyp(y, q).items()}
    z = jnp.asarray(kmeans(mu0, m, iters=5))
    s0 = np.full_like(mu0, 0.5)

    def engine(backend, rows=n, chunk=chunk, psi2_fn=None):
        eng = DistributedGP(mesh, latent=True, chunk_size=chunk,
                            kernel_backend=backend, psi2_fn=psi2_fn)
        data, w = eng.put_data(y=y[:rows], mu=mu0[:rows], s=s0[:rows])
        return eng, data, (w, jnp.ones((eng.n_shards,)),
                           jnp.asarray(float(rows)))

    progs = {}
    for backend in ("pallas", "xla"):
        eng, data, fixed = engine(backend)
        progs[backend] = (jax.jit(eng.bound_fn(d)), data, fixed)

    def bound_at(backend, hyp):
        fn, data, fixed = progs[backend]
        return fn(hyp, z, data["y"], data["mu"], data["s"], *fixed)

    # Unit lengthscales, where a float32 D resolves Kmm: the bound at the
    # start, fused and XLA.  Forward only at full size: the XLA psi2's
    # backward keeps (chunk, m, m, q) residuals per block, more than the
    # chip's 16 GB at this width.
    hyp = dict(hyp0, log_ell=jnp.zeros(q))
    bounds = {}
    for backend in progs:
        bounds[backend], dt = timed(bound_at, backend, hyp)
        log(f"gplvm/bound ({backend}) at the start: "
            f"{float(bounds[backend])!r}; first call {dt:.3f} s")
    ck.within("gplvm/bound_fused_vs_xla_rel",
              rel_err(bounds["pallas"], bounds["xla"]), GPLVM_RTOL)

    # default_hyp starts at lengthscale sqrt(q) on the unit-variance PCA
    # latents, where Kmm is so ill-conditioned that a float32 D makes
    # Bmat indefinite: the fused bound holds to the float64 XLA bound.
    try:
        fused = float(bound_at("pallas", hyp0))
    except Exception as e:                   # noqa: BLE001 — the guard
        fused = float("nan")
        log(f"gplvm/bound (pallas) at default_hyp raised: {e}")
        _drain_effects()
    xla = float(bound_at("xla", hyp0))
    ck.true("gplvm/default_start_xla_finite", bool(np.isfinite(xla)),
            f"xla bound={xla!r}")
    ck.within("gplvm/default_start_fused_vs_xla_rel", rel_err(fused, xla),
              GPLVM_RTOL)

    # Value and gradients, fused against XLA, on the first rows, where the
    # XLA backward fits; and the XLA gradients with its exact D rounded to
    # float32 (forward only), the error any float32 D carries here.
    def rounded_psi2(hyp, z, mu, s, w):
        exact = as_kernel(None).psi2(hyp, z, mu, s, w)
        return exact + jax.lax.stop_gradient(
            exact.astype(jnp.float32).astype(exact.dtype) - exact)

    grads = {}
    for name, backend, psi2_fn in (("pallas", "pallas", None),
                                   ("xla", "xla", None),
                                   ("xla_f32_d", "xla", rounded_psi2)):
        eng, data, fixed = engine(backend, rows=slice_rows, chunk=256,
                                  psi2_fn=psi2_fn)
        vg = eng.make_value_and_grad(d, argnums=(0, 1, 2))
        grads[name], dt = timed(vg, hyp, z, data["mu"], data["s"],
                                data["y"], *fixed)
        log(f"gplvm/{slice_rows}-row slice ({name}): bound "
            f"{-float(grads[name][0])!r}; first call {dt:.3f} s")
    (v1, g1), (vx, gx) = grads["pallas"], grads["xla"]
    ck.within("gplvm/slice_bound_fused_vs_xla_rel", rel_err(v1, vx),
              GPLVM_RTOL)
    floor = _tree_rel(grads["xla_f32_d"][1], gx)
    log(f"gplvm/slice gradients: fused vs XLA {_tree_rel(g1, gx)!r}, "
        f"XLA with float32 D vs XLA {floor!r}")
    ck.within("gplvm/slice_grad_fused_vs_xla_over_f32_floor",
              _tree_rel(g1, gx) / floor, GPLVM_GRAD_FACTOR)

    eng, data, fixed = engine("pallas")
    vg = eng.make_value_and_grad(d, argnums=(0, 1, 2))
    rest = (data["s"], data["y"], *fixed)
    params = (hyp, z, data["mu"])
    _, dt0 = timed(vg, *params, *rest)
    text = vg.lower(*params, *rest).compile().as_text()
    ck.true("gplvm/step_has_tpu_custom_call", has_kernel(text))
    opt = (jax.tree.map(jnp.zeros_like, params),) * 2
    times = []
    for i in range(steps):
        (v, g), dt = timed(vg, *params, *rest)
        times.append(dt)
        ck.true(f"gplvm/step{i}_finite", all_finite((v, g)),
                f"bound={-float(v)!r}")
        params, opt = adam(params, g, opt, i + 1, lr=1e-2)
    log(f"gplvm/value_and_grad n={n} d={d} q={q} m={m}: first call "
        f"{dt0:.3f} s, steady {min(times):.4f} s")


def _drain_effects():
    """A failed host callback (the definiteness guard) leaves its error on
    JAX's effect tokens, which would report it again at exit; take it."""
    import jax
    try:
        jax.effects_barrier()
    except Exception:                        # noqa: BLE001 — the same error
        pass


def phase_reference(ck: Checks, mesh, hyp, z, rows, chunk):
    import jax
    import jax.numpy as jnp

    from repro.core import DistributedGP
    from repro.core.bound import DEFAULT_JITTER
    from repro.core.ref_naive import titsias_bound_direct
    from repro.data.synthetic import flight_like

    src = flight_like(n=rows, seed=0)
    eng = DistributedGP(mesh, chunk_size=chunk, kernel_backend="pallas")
    bound = eng.streamed_bound(hyp, z, eng.put_data(stream=src), d=1)
    cpu = jax.devices("cpu")[0]
    data = src.read(0, rows)
    with jax.default_device(cpu):
        h = jax.tree.map(lambda a: jnp.asarray(jax.device_get(a)), hyp)
        ref, dt = timed(jax.jit(titsias_bound_direct, static_argnames=(
            "jitter",)), h, jnp.asarray(data["mu"]), jnp.asarray(data["y"]),
            jnp.asarray(jax.device_get(z)), jitter=DEFAULT_JITTER)
    log(f"reference/{rows}-row slice: fused bound {float(bound)!r}, "
        f"f64 direct bound {float(ref)!r} (cpu, {dt:.2f} s)")
    ck.within("reference/bound_vs_f64_direct_rel", rel_err(bound, ref),
              BOUND_RTOL)


# -- four chips ---------------------------------------------------------------

def phase_multichip(ck: Checks, n, m, chunk, batch_blocks):
    """The 4-chip data mesh against device 0 alone, in both reduce modes.

    ``serial`` and ``overlap`` compute the same bound and gradients (they
    differ in when the psum runs and so in how the f64 sums associate), so
    one device-0 baseline of each quantity serves both modes."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DistributedGP
    from repro.core.bound import collapsed_bound
    from repro.core.stats import Stats, partial_stats_chunked
    from repro.data.synthetic import flight_like
    from repro.launch.mesh import make_compat_mesh

    mesh4 = make_compat_mesh((4,), ("data",))
    mesh1 = make_compat_mesh((1,), ("data",))
    src = flight_like(n=n, seed=0)
    hyp, z = flight_init(src, m)
    rows = src.read(0, n)
    nf = jnp.asarray(float(n))
    key = jax.random.PRNGKey(7)

    def step_program(mesh, mode, batch_blocks=None):
        eng = DistributedGP(mesh, chunk_size=chunk, kernel_backend="pallas",
                            reduce_mode=mode, batch_blocks=batch_blocks)
        data, w = eng.put_data(y=rows["y"], mu=rows["mu"])
        args = (hyp, z, data["mu"], None, data["y"], w,
                jnp.ones((eng.n_shards,)), nf)
        if batch_blocks is not None:
            args += (key,)
        return eng, data, w, eng.make_value_and_grad(1), args

    # Every shard of an SVI step samples batch_blocks of its own blocks with
    # the step key folded by its shard index.  Device 0 computes the same
    # estimator directly from the 4-shard layout: the shards' sampled Stats
    # summed, then the same collapsed bound (drop mode, n = n_full).
    eng4, data4, w4, _, _ = step_program(mesh4, "serial", batch_blocks)
    placed = {s.device for s in data4["mu"].addressable_shards}
    ck.true("multichip/shards_on_4_devices", len(placed) == 4,
            f"devices={sorted(d.id for d in placed)}")
    dev0 = jax.devices()[0]
    y0, mu0, w0 = (jax.device_put(np.asarray(a), dev0)
                   for a in (data4["y"], data4["mu"], w4))
    per = y0.shape[0] // 4

    def neg_svi(hyp, z, y0, mu0, w0):
        parts = [partial_stats_chunked(
            hyp, z, y0[k * per:(k + 1) * per], mu0[k * per:(k + 1) * per],
            None, weights=w0[k * per:(k + 1) * per], latent=False,
            reg_stats_fn=eng4.reg_stats_fn, block_size=chunk,
            batch_blocks=batch_blocks, key=jax.random.fold_in(key, k),
            force_scan=True) for k in range(4)]
        st = Stats(*(sum(t) for t in zip(*parts)))._replace(n=nf)
        return -collapsed_bound(hyp, z, st, 1)

    progs = {"exact/device0": step_program(mesh1, "serial")[3:],
             "svi/device0": (jax.jit(jax.value_and_grad(neg_svi, argnums=(0, 1))),
                             (*jax.device_put((hyp, z), dev0), y0, mu0, w0))}
    for mode in ("serial", "overlap"):
        progs[f"exact/{mode}/4chips"] = step_program(mesh4, mode)[3:]
        progs[f"svi/{mode}/4chips"] = step_program(mesh4, mode,
                                                 batch_blocks)[3:]
    # Trace here, compile the six programs at once (XLA releases the GIL).
    lowered = {k: f.lower(*a) for k, (f, a) in progs.items()}
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(lowered)) as pool:
        compiled = dict(zip(lowered, pool.map(lambda lo: lo.compile(),
                                              lowered.values())))
    log(f"multichip: {len(compiled)} programs compiled in "
        f"{time.perf_counter() - t0:.1f} s")

    out = {}
    for name, exe in compiled.items():
        args = progs[name][1]
        _, dt0 = timed(exe, *args)
        out[name], dt = timed(exe, *args)
        log(f"multichip/{name}: bound {-float(out[name][0])!r}; "
            f"first run {dt0:.4f} s, second {dt:.4f} s")
    for mode in ("serial", "overlap"):
        for kind in ("exact", "svi"):
            (v4, g4), (v1, g1) = out[f"{kind}/{mode}/4chips"], \
                out[f"{kind}/device0"]
            ck.within(f"multichip/{kind}/{mode}/bound_rel", rel_err(v4, v1),
                      MESH_BOUND_RTOL)
            ck.within(f"multichip/{kind}/{mode}/grad_rel", _tree_rel(g4, g1),
                      MESH_GRAD_RTOL)

    # The streamed exact bound (host-fed chunks; its reduce is always the
    # single post-scan psum, whatever reduce_mode says).
    bounds = {}
    for mesh in (mesh4, mesh1):
        eng = DistributedGP(mesh, chunk_size=chunk, kernel_backend="pallas")
        b, dt = timed(eng.streamed_bound, hyp, z, eng.put_data(stream=src),
                      d=1)
        bounds[mesh.size] = b
        log(f"multichip/streamed_bound/{mesh.size}dev: {float(b)!r} "
            f"({dt:.3f} s)")
    ck.within("multichip/streamed_bound_rel", rel_err(bounds[4], bounds[1]),
              MESH_BOUND_RTOL)


def _tree_rel(a, b) -> float:
    """Largest leaf-wise max|a - b| / max|b|; a leaf whose gradient is
    near zero is measured against 1e-6 of the largest leaf instead."""
    import jax
    import numpy as np
    xs = [np.asarray(x) for x in jax.tree.leaves(a)]
    ys = [np.asarray(y) for y in jax.tree.leaves(b)]
    top = max(float(np.max(np.abs(y))) for y in ys)
    return max(float(np.max(np.abs(x - y)))
               / max(float(np.max(np.abs(y))), 1e-6 * top)
               for x, y in zip(xs, ys))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401  (float64 on; see repro/__init__.py)
    except ImportError:
        print("chip_smoke: the repro package is not next to this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              "runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")

    from repro.configs.gp_paper import GP_CONFIGS
    from repro.launch.mesh import make_compat_mesh

    ck = Checks()
    if args.chips == 4:
        ck.phase("multichip", phase_multichip, n=FLIGHT["n"], m=FLIGHT["m"],
                 chunk=FLIGHT["chunk"], batch_blocks=8)
    else:
        mesh = make_compat_mesh((1,), ("data",))
        out = ck.phase("flight", phase_flight, mesh, **FLIGHT)
        if out is not None:
            hyp, z, state = out
            ck.phase("serving", phase_serving, state, **SERVE)
            ck.phase("reference", phase_reference, mesh, hyp, z,
                     rows=REF_ROWS, chunk=FLIGHT["chunk"])
        usps = GP_CONFIGS["gplvm-usps"]
        ck.phase("gplvm", phase_gplvm, mesh, n=usps.n, d=usps.d, q=usps.q,
                 m=usps.m, chunk=512, steps=3, slice_rows=512)

    if ck.failed:
        log(f"FAILED: {', '.join(ck.failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
