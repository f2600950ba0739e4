"""Drive whole benchmark runs on the CPU at a size a test run can hold:
the chip check is skipped, everything else is the run's own path."""
from __future__ import annotations

import time

from bench import harness, manifest

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
TINY = {"sgpr": dict(n=6000, m=16, chunk_size=512, reference_block=512),
        "gplvm": dict(n=300, m=16, q=5, chunk_size=128, reference_block=64)}


def with_held_out(man: dict, name: str) -> dict:
    """The manifest with a cell held out of it (its entries kept in
    ``bench/workloads/<name>.json`` under ``held_out``) put back."""
    import json

    held = json.loads((manifest.BENCH / "workloads" / f"{name}.json")
                      .read_text())["held_out"]
    man = json.loads(json.dumps(man))
    man["configs"].append(held["config"])
    man["workloads"].append(held["workload"])
    man["per_layer"] += held["per_layer"]
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in held["listed_by"]:
            m["workloads"].append(name)
    return man


def cell(name: str) -> dict:
    man = manifest.load()
    if name not in {w["name"] for w in man["workloads"]}:
        man = with_held_out(man, name)
    c = manifest.cell(man, name)
    c["config"].update(TINY[c["config"]["model"]])
    return c


def run(name: str, seed: int = 2**31 + 17, seconds: float = 0.2) -> dict:
    return harness.run(cell(name), seed, seconds, None, time.perf_counter(),
                       PEAK)


def plant(monkeypatch, fault: str) -> None:
    """Break the timed path underneath the run."""
    import jax

    from repro.core import distributed
    from repro.core.distributed import DistributedGP

    from bench.traffic import _shared

    if fault == "unchanged":
        monkeypatch.setattr(_shared, "adam_update",
                            lambda params, grads, state, t, lr:
                            (params, state))
    elif fault == "half_batch":
        orig = distributed.partial_stats_chunked

        def half(hyp, z, y, mu, s=None, weights=None, **kw):
            w = weights.at[weights.shape[0] // 2:].set(0.0)
            return orig(hyp, z, y, mu, s, weights=w, **kw).scale(2.0)

        monkeypatch.setattr(distributed, "partial_stats_chunked", half)
    elif fault == "altered":
        def wrap(method):
            def build(self, *a, **kw):
                fn = method(self, *a, **kw)

                def altered(*args, **kwargs):
                    v, g = fn(*args, **kwargs)
                    return v * 1.001, jax.tree.map(lambda x: x * 1.001, g)
                return altered
            return build

        for name in ("make_value_and_grad", "streamed_svi_value_and_grad"):
            monkeypatch.setattr(DistributedGP, name,
                                wrap(getattr(DistributedGP, name)))
    elif fault == "no_exchange":
        monkeypatch.setattr(DistributedGP, "_psum_stats",
                            lambda self, st: st)
    else:
        raise ValueError(fault)
