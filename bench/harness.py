"""One run of one training cell: set-up, the checked steps, the measured
window, the trace, and the comparison with the reference.

Set-up builds the runner (data from the seed, the program's engine and its
compiled step) and drives it through its first ``CHECKED_STEPS`` steps,
which compile or load every program the window runs.  The window then
runs whole iterations until ``seconds`` have passed; it ends with the
last iteration, and the rate is the rows of all its iterations over all
its time.  A traced run profiles the window's first iterations only.
After the window the peak memory is read, the program's state is
dropped, and the reference follows the checked steps from the same start
on the same rows.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from bench import compare, manifest
from bench.reference import training as reference

CHECKED_STEPS = 3
# A traced run traces the window's first iterations, at least this many
# and this long: a trace holds tens of MB per second of the exact step,
# and writing it out takes seconds per MB.
TRACE_ITERATIONS, TRACE_SECONDS = 2, 0.25


def checked_steps(drv) -> dict:
    """The runner's first ``CHECKED_STEPS`` iterations, read as
    ``compare.readings`` takes them: each loss, the first gradient (from
    the optimiser's state after step 1), the parameters' change."""
    losses = [drv.iteration()]
    grad1 = drv.first_gradient()
    losses += [drv.iteration() for _ in range(CHECKED_STEPS - 1)]
    return {"losses": losses, "grad1": grad1,
            "change": jax.tree.map(np.subtract, drv.params_host(),
                                   drv.start)}


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell: dict, seed: int, seconds: float, trace_dir: Path | None,
        t0: float, peak: dict) -> dict:
    """Returns the result line's dict, ``checks`` last."""
    config, mix = cell["config"], cell["mix"]
    drv_mod = manifest.runner(mix["kind"])
    drv = drv_mod.Runner(config, mix, seed, jax.devices())
    prog = checked_steps(drv)
    setup_s = time.perf_counter() - t0

    tracing = trace_dir is not None
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    iters, traced, failed = 0, 0, 0
    start = time.perf_counter()
    while True:
        loss = drv.iteration()
        iters += 1
        failed += not math.isfinite(loss)
        elapsed = time.perf_counter() - start
        if tracing and iters >= TRACE_ITERATIONS and \
                elapsed >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing, traced = False, iters
        if elapsed >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
        traced = iters
    memory = peak_memory(drv.devices)
    chips = len(drv.devices)
    rows = drv.rows_per_iteration
    feeds = drv.reference_feeds(CHECKED_STEPS)
    start_params, lr = drv.start, drv.lr
    drv.close()
    del drv
    gc.collect()

    ref = reference.train(start_params, feeds, d=config["d"],
                          jitter=config["jitter"],
                          block=config["reference_block"],
                          lr=lr)
    ok, checks = compare.judge(compare.readings(prog, ref), cell["limits"])

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    out = {"correct": ok and failed == 0, "attempted": iters,
           "failed": failed, "metrics": {}, "device": device}
    if trace_dir is None:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        out["metrics"][drv_mod.METRIC] = {
            "value": iters * rows / elapsed, "unit": units[drv_mod.METRIC]}
        out["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        from bench import trace as tr

        red = tr.reduce_dir(trace_dir, chips)
        ctx = {"trace": red, "iterations": traced, "chips": chips,
               "config": config, "mix": mix, "peak": peak,
               "rows_per_iteration": rows}
        for m in cell["per_layer"]:
            v = manifest.reader(m["name"]).read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = tr.breakdown(red)
    out["checks"] = checks
    return out
