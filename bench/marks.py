"""The program's own marks in a profiler trace: its named scopes on the
device ops and its host spans, beside what ``bench/trace.py`` reads.

``load`` reads the same ``.xplane.pb`` as ``trace.load`` and keeps, besides
the ops and spans, where each op comes from: its HLO module (the device's
``XLA Modules`` line) and the ``op_name`` path of its instruction, read
from the HLO protos the profiler stores in the trace.  The path holds the
program's ``jax.named_scope``s (``map``, ``global_step``) and, for the
backward, a ``transpose(`` component.  It also keeps the program's host
spans (``PROGRAM_SPANS``) and their arguments.  ``reduce`` gives a
``Marked``: ``trace.reduce``'s window, devices and spans, with the time of
each scope and the idle time under each host span.  ``of`` finds the
trace a traced run wrote and reduces it, for the per-layer readers.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field
from pathlib import Path

from bench import trace
from bench.manifest import ROOT

MODULES_LINE = "XLA Modules"
# core/distributed.py streamed_svi_value_and_grad, data/stream.py
PROGRAM_SPANS = ("svi_sample", "chunk_assemble", "svi_h2d", "svi_dispatch")
SPANS = trace.SPANS + PROGRAM_SPANS
# core/stats.py partial_stats_chunked, core/bound.py collapsed_bound
SCOPES = ("map", "global_step")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            step = 8 if wire == 1 else 4
            v, i = buf[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_op_names(raw: bytes) -> dict:
    """``{module: {instruction: op_name}}`` from the HLO protos that the
    profiler keeps in the ``/host:metadata`` plane of an XSpace, keyed by
    the module's name as the ``XLA Modules`` line gives it
    (``jit_neg(<program id>)``).  ``ProfileData`` does not expose them,
    and the TPU's op events carry no ``op_name``, so the few fields needed
    are read from the wire format: XSpace.planes (1); XPlane.name (2),
    .event_metadata (4), .stat_metadata (5); XEventMetadata.name (2),
    .stats (5); XStat.metadata_id (1), .bytes_value (6);
    HloProto.hlo_module (1); HloModuleProto.computations (3);
    HloComputationProto.instructions (2); HloInstructionProto.name (1),
    .metadata (7); OpMetadata.op_name (2)."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        parts = {}
        for pf, v in _fields(plane):
            parts.setdefault(pf, []).append(v)
        if _text(parts.get(2, [b""])[0]) != "/host:metadata":
            continue
        stat_names = {}
        for entry in parts.get(5, []):
            for ef, meta in _fields(entry):
                if ef == 2:
                    m = dict(_fields(meta))
                    stat_names[m.get(1, 0)] = _text(m.get(2, b""))
        for entry in parts.get(4, []):
            for ef, meta in _fields(entry):
                if ef != 2:
                    continue
                name, protos = "", []
                for mf, v in _fields(meta):
                    if mf == 2:
                        name = _text(v)
                    elif mf == 5:
                        st = dict(_fields(v))
                        if stat_names.get(st.get(1)) == "Hlo Proto" and \
                                6 in st:
                            protos.append(st[6])
                for proto in protos:
                    for hf, module in _fields(proto):
                        if hf == 1:
                            out[name] = _instruction_op_names(module)
    return out


def _instruction_op_names(module) -> dict:
    names = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for cf, inst in _fields(comp):
            if cf != 2:
                continue
            name, path = "", ""
            for inf, v in _fields(inst):
                if inf == 1:
                    name = _text(v)
                elif inf == 7:
                    for mf, mv in _fields(v):
                        if mf == 2:
                            path = _text(mv)
            names[name] = path
    return names


TRANSFORM = re.compile(r"^(?!jit\()[\w-]+\((.*)\)$")


@functools.lru_cache(maxsize=None)
def scope(path: str) -> str:
    """The program scope an ``op_name`` path lies in: ``map`` or
    ``global_step``, with ``_bwd`` where the path holds JAX's mark of the
    backward, ``transpose(``; "" for none.  JAX writes a scope that a
    transformation starts in inside its mark (``jvp(map)``,
    ``transpose(jvp(global_step))``), and one inside a ``shard_map`` as a
    component of its own (``transpose(jvp())/shard_map/map``)."""
    for part in path.split("/"):
        while TRANSFORM.match(part):
            part = TRANSFORM.match(part).group(1)
        if part in SCOPES:
            return part + ("_bwd" if "transpose(" in path else "")
    return ""


def _module_at(modules: list, t: float) -> str:
    """The module whose ``XLA Modules`` event holds time ``t``."""
    for name, a, b in modules:
        if a <= t < b:
            return name
    return ""


def load(path) -> dict:
    """``trace.load``'s ``{"devices": ..., "spans": ...}``, the spans those
    of ``SPANS`` (``[name, start_ns, end_ns]``, with ``[args]`` appended
    where the span has arguments), and ``"where": {id: [i, ...]}``,
    aligned with each device's ops, indexing ``"paths": [[module,
    op_name], ...]``."""
    from jax.profiler import ProfileData

    raw_bytes = Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(raw_bytes)
    op_names = hlo_op_names(raw_bytes)
    devices, spans, kinds, where, paths = {}, [], {}, {}, {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            modules = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = sorted([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns]
                                     for e in line.events)
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                ops, seen = [], {}
                for e in line.events:
                    a = e.start_ns
                    module = _module_at(modules, a)
                    info = seen.get((module, e.name))
                    if info is None:
                        kind = kinds.get(e.name)
                        if kind is None:  # an op's kind is fixed by its name
                            kind = kinds[e.name] = trace.op_kind(
                                e.name, dict(e.stats))
                        name = trace.op_name(e.name)
                        key = (module, op_names.get(module, {}).get(name, ""))
                        info = seen[module, e.name] = (
                            name, kind, paths.setdefault(key, len(paths)))
                    name, kind, at = info
                    ops.append(([name, a, a + e.duration_ns, kind], at))
                ops.sort(key=lambda o: (o[0][1], -o[0][2]))
                devices[dev] = [o for o, _ in ops]
                where[dev] = [i for _, i in ops]
                trace._mark_parents(devices[dev])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        span = [e.name, e.start_ns, e.start_ns + e.duration_ns]
                        args = {k: v for k, v in e.stats
                                if not k.startswith("_")}
                        spans.append(span + [args] if args else span)
    return {"devices": devices, "spans": spans, "where": where,
            "paths": [list(k) for k in sorted(paths, key=paths.get)]}


@dataclass
class Marked(trace.Reduced):
    where: dict = field(default_factory=dict)   # id -> [(module, op_name)]

    def scope_s(self, *scopes: str) -> float:
        """Seconds in which an op of one of ``scopes`` (as ``scope`` names
        them: ``map``, ``map_bwd``, ``global_step``, ``global_step_bwd``,
        or "" for none) ran and no kernel or collective did, averaged over
        the devices: the ``other`` time of those scopes.  An enclosing op
        (the scan's loop) counts with its scope."""
        total = 0.0
        for dev, d in self.devices.items():
            mine = trace.union((op[1], op[2]) for op, (_, path) in
                               zip(d.ops, self.where[dev])
                               if scope(path) in scopes)
            total += trace.length(mine) - trace.length(
                trace.intersect(mine, d.work()))
        return total * 1e-9 / max(len(self.devices), 1)

    def idle_by_span(self, device: int | None = None) -> dict:
        """Idle seconds of one device (the first by default), each put
        down to the innermost span open then (the span's self time);
        ``outside_iteration`` where none is."""
        gaps, out, j = trace.idle_gaps(self, device), {}, 0
        for name, a, b in _innermost(self.spans, self.window):
            while j < len(gaps) and gaps[j][1] <= a:
                j += 1
            k = j
            while k < len(gaps) and gaps[k][0] < b:
                t = min(b, gaps[k][1]) - max(a, gaps[k][0])
                out[name] = out.get(name, 0.0) + t * 1e-9
                k += 1
        return out

    def span_union(self, *names: str) -> list:
        """The union of the intervals of the spans named ``names``."""
        return trace.union((s[1], s[2]) for s in self.spans if s[0] in names)


def _innermost(spans, window) -> list:
    """Disjoint pieces of ``window``, each with the innermost span open
    over it (the shortest one holding it): a span's self time is the
    pieces that carry its name."""
    lo, hi = window
    edges = sorted({lo, hi} | {t for s in spans for t in s[1:3]
                               if lo < t < hi})
    out = []
    for a, b in zip(edges, edges[1:]):
        inside = [s for s in spans if s[1] <= a and b <= s[2]]
        name = min(inside, key=lambda s: s[2] - s[1])[0] if inside \
            else "outside_iteration"
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1][2] = b
        else:
            out.append([name, a, b])
    return [tuple(p) for p in out]


def reduce(raw: dict, chips: int) -> Marked:
    """``trace.reduce``, with each kept op's (module, op_name) beside it;
    ("", "") for every op of a recording without them."""
    red = trace.reduce(raw, chips)
    lo, hi = red.window
    paths = [tuple(p) for p in raw.get("paths", [])]
    where = {}
    for dev_id in sorted(raw["devices"], key=int)[:chips]:
        at = raw.get("where", {}).get(dev_id)
        where[int(dev_id)] = [
            paths[at[i]] if at else ("", "")
            for i, op in enumerate(raw["devices"][dev_id])
            if op[2] > lo and op[1] < hi]
    return Marked(window=red.window, devices=red.devices, spans=red.spans,
                  where=where)


_LAST: list = [None, None]      # (file, mtime, chips), its Marked


def of(ctx) -> Marked | None:
    """The ``Marked`` of the trace a reader's ``ctx`` was reduced from: the
    newest trace file under ``.bench/trace/`` (where ``bench/run.py``
    writes it), if its window is ``ctx["trace"]``'s; None otherwise.  Read
    once for all the readers of a run."""
    red = ctx["trace"]
    if isinstance(red, Marked):
        return red
    files = glob.glob(str(ROOT / ".bench" / "trace" / "*" / "plugins" /
                          "profile" / "*" / "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path), ctx["chips"])
    if _LAST[0] != key:
        _LAST[:] = [key, reduce(load(path), ctx["chips"])]
    marked = _LAST[1]
    return marked if marked.window == red.window else None
