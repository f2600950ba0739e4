"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip: the sound program over many seeds (the lower reading),
the control (the reference computed in float32 in the program's place)
and the planted faults over a few (the upper reading).

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--out readings.json]

Each program seed builds the cell's runner, takes its checked steps, and
compares them with the float64 reference, as a run of the benchmark does
(without the window).  On the control seeds the same feeds also go
through the reference in float32, and through the reference with each
fault of a training cell planted in it:

* ``unchanged``: the update returns its state unchanged;
* ``half_batch``: half of the rows left out, the statistics of the rest
  doubled;
* ``no_exchange`` (several chips): each chip's bound from its own rows'
  statistics, without the cross-chip sum;
* ``altered``: the answer altered where it is produced, the loss and
  every gradient 0.1% off.

The benchmark's own runs do not run this.  Exit status 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def half_batch(feeds):
    out = []
    for f in feeds:
        w = f["w"].copy()
        w[w.size // 2:] = 0.0
        out.append(dict(f, w=w, scale=2.0 * f["scale"]))
    return out


def no_exchange(feeds, shards: int, block: int):
    """Chip 0's rows only: the first shard of the padded layout, whole
    blocks of ``block`` rows."""
    out = []
    for f in feeds:
        w = f["w"].copy()
        w[-(-w.size // (shards * block)) * block:] = 0.0
        out.append(dict(f, w=w))
    return out


def altered(result, rel: float = 1e-3):
    import jax

    return {"losses": [v * (1 + rel) for v in result["losses"]],
            "grad1": jax.tree.map(lambda g: g * (1 + rel), result["grad1"]),
            "change": result["change"]}


def readings_for_seed(cell, seed, with_controls: bool, **runner_kw):
    import gc

    import jax
    import numpy as np

    from bench import compare, harness, manifest
    from bench.reference import training as reference

    config, mix = cell["config"], cell["mix"]
    drv = manifest.runner(mix["kind"]).Runner(config, mix, seed,
                                              jax.devices(), **runner_kw)
    step = getattr(drv, "step", None)
    prog = harness.checked_steps(drv)
    feeds = drv.reference_feeds(harness.CHECKED_STEPS)
    start, lr = drv.start, drv.lr
    drv.close()
    del drv
    gc.collect()

    kw = dict(d=config["d"], jitter=config["jitter"],
              block=config["reference_block"], lr=lr)
    t = time.perf_counter()
    ref = reference.train(start, feeds, **kw)
    out = {"seed": seed, "reference_s": time.perf_counter() - t,
           "program": compare.readings(prog, ref), "step": step}
    if with_controls:
        out["control_f32"] = compare.readings(
            reference.train(start, feeds, dtype=np.float32, **kw), ref)
        out["unchanged"] = compare.readings(
            reference.train(start, feeds, fault="unchanged", **kw), ref)
        out["half_batch"] = compare.readings(
            reference.train(start, half_batch(feeds), **kw), ref)
        if mix["mesh"] > 1:
            out["no_exchange"] = compare.readings(reference.train(
                start, no_exchange(feeds, mix["mesh"], config["chunk_size"]),
                **kw), ref)
        out["altered"] = compare.readings(altered(ref), ref)
    return out


def summary(rows: list[dict]) -> dict:
    """The largest program reading and the smallest reading of each
    control or fault, per number (a non-finite reading counts as failed
    and sets nothing)."""
    import math

    from bench import compare

    out = {}
    for name in compare.NAMES:
        prog = [r["program"][name] for r in rows]
        out[name] = {"lower": max(prog)}
        for kind in ("control_f32", "unchanged", "half_batch",
                     "no_exchange", "altered"):
            vals = [r[kind][name] for r in rows if kind in r]
            vals = [v for v in vals if math.isfinite(v)]
            if vals:
                out[name][kind] = min(vals)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import repro  # noqa: F401
    import jax

    from bench import manifest
    from bench.run import check_chips, enable_compile_cache

    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if check_chips(jax, cell["chips"], peaks)[0] is None:
        print("control: no TPU with the cell's chips", file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows, reuse = [], {}
    for s in [int(s) for s in args.seeds.split(",")]:
        row = readings_for_seed(cell, s, s in controls, **reuse)
        if cell["mix"]["kind"] == "exact":
            reuse = {"step": row["step"]}
        del row["step"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    result = {"workload": args.workload, "seeds": rows,
              "summary": summary(rows)}
    print(json.dumps(result["summary"]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
