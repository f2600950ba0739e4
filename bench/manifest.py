"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads[i]``) names a configuration and a traffic mix.  The
harness reads

* ``configs[i]["file"]``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the mix's parameters, whose ``kind``
  names its runner, ``bench/traffic/<kind>.py``;
* ``bench/workloads/<cell>.json``: the limits of the comparison that
  decides ``correct``, with the readings they were set from;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a cell, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class ManifestError(ValueError):
    pass


def load(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise ManifestError(f"{what}: {path} does not exist")
    return json.loads(path.read_text())


def load_module(path: Path, what: str):
    """Import ``path`` once, as a module of its own (metric names hold
    dots, so these files are loaded by path and not by package)."""
    if not path.is_file():
        raise ManifestError(f"{what}: {path} does not exist")
    name = f"bench.{path.parent.name}.{path.stem.replace('.', '_')}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def cell(manifest: dict, name: str, root: Path = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, read from its files."""
    wl = _by_name(manifest["workloads"], name, "workload")
    cfg_entry = _by_name(manifest["configs"], wl["config"], "config")
    config = _json(root / cfg_entry["file"], f"config {cfg_entry['name']}")
    mix = _json(root / "bench" / "traffic" / f"{wl['traffic']}.json",
                f"traffic {wl['traffic']}")
    limits = _json(root / "bench" / "workloads" / f"{name}.json",
                   f"workload {name}")
    end_to_end = [m for m in manifest["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in
                                  reported else [])]
    return {"name": name, "chips": wl["chips"], "config": config,
            "mix": mix, "limits": limits["limits"],
            "end_to_end": end_to_end, "per_layer": per_layer}


def runner(kind: str, root: Path = ROOT):
    return load_module(root / "bench" / "traffic" / f"{kind}.py",
                       f"traffic kind {kind}")


def reader(metric: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{metric}.py",
                       f"metric {metric}")


def validate(manifest: dict, root: Path = ROOT) -> list[str]:
    """Every problem found with the manifest and the files it names."""
    errs = []
    names = [c["name"] for c in manifest["configs"]]
    names += [w["name"] for w in manifest["workloads"]]
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names += [m["name"] for m in metrics]
    for w in manifest["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in manifest["configs"]:
        names += c["reduced"]
    errs += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    errs += [f"bad unit {m['unit']!r}" for m in metrics
             if not UNIT.match(m["unit"])]
    for kind in ("configs", "workloads"):
        seen = [e["name"] for e in manifest[kind]]
        errs += [f"duplicate {kind} name {n}" for n in set(seen)
                 if seen.count(n) > 1]
    seen = [m["name"] for m in metrics]
    errs += [f"duplicate metric {n}" for n in set(seen) if seen.count(n) > 1]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    errs += [f"pair {p} appears twice" for p in set(pairs)
             if pairs.count(p) > 1]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            errs.append(f"{m['name']} moves unknown {m['moves']}")
    for w in manifest["workloads"]:
        try:
            c = cell(manifest, w["name"], root)
            runner(c["mix"]["kind"], root)
            for m in c["per_layer"]:
                reader(m["name"], root)
        except (ManifestError, KeyError) as e:
            errs.append(f"{w['name']}: {e}")
            continue
        if not c["per_layer"]:
            errs.append(f"{w['name']} reports no per-layer metric")
        if "setup_s" not in {m["name"] for m in c["end_to_end"]} or len(
                c["end_to_end"]) < 2:
            errs.append(f"{w['name']} lacks setup_s or a second metric")
    return errs
