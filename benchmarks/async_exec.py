"""Overlapped & asynchronous execution benchmarks (``--only async``).

Three measurements:

  * ``async/overlap_*`` — wall-clock of the in-mesh step under
    ``reduce_mode="serial" | "overlap" | "overlap_eager"`` on a data mesh
    over every device of this process (``jax.devices()``: the chips of a
    TPU host, or a forced CPU fleet under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  It runs in
    the benchmark's own process, which already holds the devices.  On the
    CPU the psum is a memcpy, so overlap is reported for structure
    validation, not gated — the scheduling win needs real interconnect
    latency to hide.
  * ``async/step_*`` — the gated number: per-step wall-clock of the
    barrier-free ``AsyncEngine`` (refresh r of K shards per step, stale
    fold for the rest) against the synchronous serial map-reduce step on
    the same mesh and data.  The async step maps r/K of the rows, so its
    speedup is honest work reduction (bounded-staleness gradients are the
    price; docs/training.md quantifies it).  Gate: >= 1.15x at n >= 512k
    with refresh=1; at that size the benchmark refuses a one-device
    process rather than skip the gate.
  * ``async/straggler_*`` — goodput under straggler injection in the
    established host-simulated idiom (gp_common/fig5/fig7): each shard is
    slowed by ``straggler_factor`` with probability ``rate`` per
    iteration.  The synchronous iteration waits for max(shard times) —
    it stalls whenever ANY shard straggles (prob 1-(1-rate)^K) — while
    the async step stalls only when the ONE refreshed shard straggles
    (prob rate).  Goodput = fresh rows folded per second; the curve
    reproduces the paper's fig. 7 shape: graceful async degradation
    vs collapsing synchronous throughput as the failure rate grows.
"""
from __future__ import annotations

import time

import numpy as np


# --------------------------------------------------------------------------
# the mesh comparison: every device this process holds
# --------------------------------------------------------------------------

def _mesh_steps(n: int, m: int, chunk: int, iters: int, refresh_sweep,
                staleness: int):
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import DistributedGP
    from repro.distributed.async_stats import AsyncEngine
    from repro.launch.mesh import make_compat_mesh

    from .gp_common import default_hyp

    shards = len(jax.devices())
    if n >= 512_000 and shards < 2:
        raise RuntimeError(
            f"the async step's 1.15x gate (n={n}) compares a mesh of several "
            f"devices, and this process has {shards}; on the CPU run it under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    q, d = 2, 1
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, q))
    y = rng.standard_normal((n, d))
    hyp = default_hyp(q)
    z = jnp.asarray(rng.standard_normal((m, q)))
    nf = jnp.asarray(float(n))

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)      # warm (compile)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    mesh = make_compat_mesh((shards,), ("data",))
    rows = []
    t_modes = {}
    for mode in ("serial", "overlap", "overlap_eager"):
        eng = DistributedGP(mesh, chunk_size=chunk, reduce_mode=mode)
        data, w = eng.put_data(y=y, mu=x)
        vg = eng.make_value_and_grad(d)
        ones = jnp.ones((eng.n_shards,))
        t_modes[mode] = timed(vg, hyp, z, data["mu"], None, data["y"], w,
                              ones, nf)
        rows.append((f"async/overlap_mode={mode}_n={n}_shards={shards}",
                     t_modes[mode] * 1e6,
                     f"vs_serial={t_modes['serial'] / t_modes[mode]:.2f}x"))

    # --- barrier-free async step vs the serial synchronous step ------------
    per = n // shards
    shard_data = [{"y": y[k * per:(k + 1) * per],
                   "mu": x[k * per:(k + 1) * per]} for k in range(shards)]
    for r in [r for r in refresh_sweep if r <= shards]:
        eng_a = AsyncEngine(shard_data, d=d, staleness=staleness, refresh=r,
                            chunk_size=chunk)
        for _ in range(-(-shards // r)):   # populate every shard + warm jit
            eng_a.step(hyp, z)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            v, g = eng_a.step(hyp, z)
            jax.block_until_ready(v)
            ts.append(time.perf_counter() - t0)
        t_async = float(np.median(ts))
        speedup = t_modes["serial"] / t_async
        rows.append((f"async/step_refresh={r}_n={n}_shards={shards}",
                     t_async * 1e6, f"speedup={speedup:.2f}x"))
        if r == min(refresh_sweep) and n >= 512_000:
            assert speedup >= 1.15, \
                f"async step speedup {speedup:.2f}x below the 1.15x gate"
    for name, us, derived in rows:
        print(f"  {name}: {us / 1e3:.1f} ms  {derived}")
    return rows


# --------------------------------------------------------------------------
# host-simulated straggler goodput (runs in the parent process)
# --------------------------------------------------------------------------

def _straggler_goodput(n: int, shards: int, rates, factor: float,
                       iters: int, m: int):
    """Goodput (fresh rows folded per second) of sync vs async iterations
    under per-iteration straggler injection — host-simulated (one thunk
    per shard, timed individually, gp_common idiom)."""
    import jax
    import jax.numpy as jnp

    from repro.core.bound import collapsed_bound
    from repro.core.stats import Stats
    from repro.distributed.async_stats import AsyncStatsAccumulator

    from .gp_common import default_hyp, make_shard_fn, split_shards

    q, d = 2, 1
    rng = np.random.default_rng(2)
    x = rng.standard_normal((n, q))
    y = rng.standard_normal((n, d))
    hyp = default_hyp(q)
    z = jnp.asarray(rng.standard_normal((m, q)))
    fn = make_shard_fn(hyp, z, d, latent=False)
    shard_list = split_shards(y, x, None, shards)
    per = n // shards

    def collapse(st):
        b = collapsed_bound(hyp, z, st._replace(n=jnp.asarray(float(n))),
                            d)
        jax.block_until_ready(b)
        return b

    # warm the map and collapse jits, then calibrate the straggler sleep
    # off the warm map time (floored so it dominates per-step host
    # overhead even at smoke sizes)
    parts = [fn(*sh) for sh in shard_list]
    tot = parts[0]
    for p in parts[1:]:
        tot = Stats(*(a + b for a, b in zip(tot, p)))
    collapse(tot)                       # warms map + fold + collapse jits
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(fn(*shard_list[0]).D)
    t_map = (time.perf_counter() - t0) / 3
    sleep_s = max(t_map * (factor - 1.0), 0.005)

    rows = []
    srng = np.random.default_rng(7)
    for rate in rates:
        # synchronous: every shard maps, the iteration waits for the max
        t_sync = []
        for _ in range(iters):
            times = []
            parts = []
            for sh in shard_list:
                t1 = time.perf_counter()
                if srng.uniform() < rate:
                    time.sleep(sleep_s)
                st = fn(*sh)
                jax.block_until_ready(st.D)
                times.append(time.perf_counter() - t1)
                parts.append(st)
            t1 = time.perf_counter()
            tot = parts[0]
            for p in parts[1:]:
                tot = Stats(*(a + b for a, b in zip(tot, p)))
            collapse(tot)
            t_sync.append(max(times) + (time.perf_counter() - t1))
        g_sync = n / float(np.mean(t_sync))

        # async: refresh ONE shard, fold it against the stale rest
        acc = AsyncStatsAccumulator(staleness=2 * shards, reweight="drop")
        for k, sh in enumerate(shard_list):
            acc.push(k, fn(*sh), stamp=0)
        t_async = []
        for it in range(iters * shards):
            k = it % shards
            t1 = time.perf_counter()
            if srng.uniform() < rate:
                time.sleep(sleep_s)
            st = fn(*shard_list[k])
            jax.block_until_ready(st.D)
            acc.push(k, st, stamp=it + 1)
            collapse(acc.read(it + 1))
            t_async.append(time.perf_counter() - t1)
        g_async = per / float(np.mean(t_async))

        ratio = g_async / g_sync
        rows.append((f"async/straggler_rate={rate}_sync",
                     float(np.mean(t_sync)) * 1e6,
                     f"goodput={g_sync:.0f}rows/s"))
        rows.append((f"async/straggler_rate={rate}_async",
                     float(np.mean(t_async)) * 1e6,
                     f"goodput={g_async:.0f}rows/s ratio={ratio:.2f}x"))
        print(f"  straggler rate={rate:4.2f}  sync={g_sync:10.0f} rows/s  "
              f"async={g_async:10.0f} rows/s  ratio={ratio:.2f}x")
    return rows


# --------------------------------------------------------------------------
# the benchmark target
# --------------------------------------------------------------------------

def async_exec(n: int = 524_288, m: int = 32, shards: int = 8,
               chunk: int = 4096, iters: int = 3,
               refresh_sweep=(1, 2, 4, 8), staleness: int = 16,
               straggler_rates=(0.0, 0.1, 0.3),
               straggler_factor: float = 8.0, straggler_iters: int = 10,
               n_strag: int = 20_000):
    """Async/overlap execution benchmark.  The mesh comparison runs on
    every device of this process; the straggler goodput curve is
    simulated in-process over ``shards`` host shards.  Returns the usual
    (name, us_per_call, derived) rows."""
    rows = _mesh_steps(n, m, chunk, iters, refresh_sweep, staleness)
    rows.extend(_straggler_goodput(n_strag, shards, straggler_rates,
                                   straggler_factor, straggler_iters, m))
    # fig. 7 shape: the async/sync goodput ratio must GROW with the
    # straggler rate (async degrades gracefully, sync waits for the max)
    ratios = [float(r[2].split("ratio=")[1][:-1]) for r in rows
              if "ratio=" in r[2]]
    if len(ratios) >= 2:
        assert ratios[-1] >= ratios[0], \
            f"straggler ratio curve not increasing: {ratios}"
    return rows
