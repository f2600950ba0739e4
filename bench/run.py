"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  With ``--trace 0`` the result's metrics
are the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the metrics are the cell's per-layer metrics,
read from the device trace.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and ``checks`` last: every number
compared beside its limit), and the last lines of standard error repeat
the checks.

Exit status: 0 when a result was printed; 2, with no result, when the
manifest or the program cannot be found, when JAX finds no TPU or fewer
chips than the cell asks for, or when the chip's kind is not in
``bench/peaks.json``.  There is no fallback to the CPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def check_chips(jax, chips: int, peaks: dict):
    """The device and its peaks, or the reason there are none."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return None, f"no TPU: JAX found {dev.platform}"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX sees {len(devices)}"
    if dev.device_kind not in peaks:
        return None, (f"device kind {dev.device_kind!r} is not in "
                      "bench/peaks.json")
    return peaks[dev.device_kind], None


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set,
    else at ``.jax_cache/`` in the checkout; every program is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # libtpu logs under /tmp unless told otherwise: keep it in the checkout.
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench" / "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from bench import manifest

    try:
        cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    except (manifest.ManifestError, OSError) as e:
        return fail(str(e))
    try:
        import repro  # noqa: F401  (the system under test; float64 on)
    except ImportError:
        return fail("the program (src/repro) is not in this checkout")
    import jax

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    peak, why = check_chips(jax, cell["chips"], peaks)
    if peak is None:
        return fail(why)
    enable_compile_cache(jax)

    from bench import harness

    trace_dir = ROOT / ".bench" / "trace" / args.workload if args.trace \
        else None
    out = harness.run(cell, args.seed, args.seconds, trace_dir, T0, peak)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
