"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain events: for every TPU device, the operations of its ``XLA Ops``
line (name, start, end in ns, and the kind: ``kernel`` for a Pallas
kernel, ``collective`` for a cross-chip exchange, ``other`` for the rest);
and the benchmark's own host spans (``iteration`` and the spans inside
it).  ``reduce`` cuts both to the traced window, from the start of the
first ``iteration`` span to the end of the last, and gives per device the
busy union, its share of the window, and the time of each kind of op.
The events are plain tuples, so the reduction is tested on a recorded
trace kept as JSON.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("iteration", "value_and_grad", "optimizer_update")
# XLA names an instruction after its opcode unless told otherwise; a
# Pallas kernel's instruction is named after the kernel and its text holds
# its custom-call target.
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def op_name(name: str) -> str:
    """The instruction's name: the event name up to " = " (a TPU trace
    names an op event by its whole HLO text), without the leading %."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str, stats: dict) -> str:
    """``kernel`` for a Pallas (Mosaic) kernel, ``collective`` for a
    cross-chip exchange, ``other`` otherwise."""
    text = name + " " + str(stats.get("long_name", ""))
    if 'custom_call_target="tpu_custom_call"' in text:
        return "kernel"
    if COLLECTIVE.match(op_name(name)):
        return "collective"
    return "other"


def _mark_parents(ops: list) -> None:
    """Ops that enclose later ops of the same line (a while loop around its
    body) become ``parent``: they count as busy time, not as work of a
    kind of their own."""
    ops.sort(key=lambda o: (o[1], -o[2]))
    for i, op in enumerate(ops[:-1]):
        if ops[i + 1][1] < op[2] and ops[i + 1][2] <= op[2]:
            op[3] = "parent"


def load(path) -> dict:
    """``{"devices": {id: [[name, start_ns, end_ns, kind], ...]},
    "spans": [[name, start_ns, end_ns], ...]}`` from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans, kinds = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices.setdefault(int(m.group(1)), [])
                for e in line.events:
                    kind = kinds.get(e.name)
                    if kind is None:     # an op's kind is fixed by its name
                        kind = kinds[e.name] = op_kind(e.name, dict(e.stats))
                    ops.append([op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns, kind])
                _mark_parents(ops)
            elif not m and plane.name.startswith("/host"):
                spans += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                          for e in line.events if e.name in SPANS]
    return {"devices": devices, "spans": spans}


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclass
class Device:
    ops: list                 # [name, start, end, kind], cut to the window
    busy: list                # union of all ops
    by_kind: dict = field(default_factory=dict)   # kind -> union

    def work(self, kinds=("kernel", "collective")):
        return union(iv for k in kinds for iv in self.by_kind.get(k, []))


@dataclass
class Reduced:
    window: tuple[float, float]
    devices: dict             # id -> Device
    spans: list               # [name, start, end] inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(length(d.busy) for d in self.devices.values()) * 1e-9 \
            / max(len(self.devices), 1)

    def kind_s(self, kind: str) -> float:
        """Seconds in which a ``kind`` op ran, averaged over the devices;
        ``other`` is busy time in neither a kernel nor a collective."""
        return sum(length(d.busy) - length(d.work()) if kind == "other" else
                   length(d.by_kind.get(kind, [])) for d in
                   self.devices.values()) * 1e-9 / max(len(self.devices), 1)

    def ops(self, kind: str | None = None):
        for d in self.devices.values():
            for op in d.ops:
                if kind is None or op[3] == kind:
                    yield op


def reduce(raw: dict, chips: int) -> Reduced:
    """Cut the events to the window of the ``iteration`` spans and keep
    the first ``chips`` devices (the ones the cell's mesh uses)."""
    its = [s for s in raw["spans"] if s[0] == "iteration"]
    if not its:
        raise ValueError("the trace holds no 'iteration' span")
    lo, hi = min(s[1] for s in its), max(s[2] for s in its)
    devices = {}
    for dev_id in sorted(raw["devices"], key=int)[:chips]:
        ops = [[n, max(a, lo), min(b, hi), k]
               for n, a, b, k in raw["devices"][dev_id] if b > lo and a < hi]
        kinds = {}
        for n, a, b, k in ops:
            kinds.setdefault(k, []).append((a, b))
        devices[int(dev_id)] = Device(
            ops=ops, busy=union((a, b) for _, a, b, _ in ops),
            by_kind={k: union(v) for k, v in kinds.items()})
    spans = [s for s in raw["spans"] if s[2] > lo and s[1] < hi]
    return Reduced(window=(lo, hi), devices=devices, spans=spans)


def reduce_dir(trace_dir: Path, chips: int) -> Reduced:
    files = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    return reduce(load(files[0]), chips)


def idle_gaps(red: Reduced, device: int | None = None):
    """Idle intervals of one device (the first by default) in the window."""
    dev = red.devices[min(red.devices) if device is None else device]
    edges = [red.window[0]] + [t for iv in dev.busy for t in iv] + \
        [red.window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _label(red: Reduced, t: float) -> str:
    """The innermost benchmark span open at host time ``t``."""
    inside = [s for s in red.spans if s[1] <= t < s[2]]
    if not inside:
        return "outside_iteration"
    return min(inside, key=lambda s: s[2] - s[1])[0]


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took most time (summed by name over the
    devices) and the longest idle gaps of the first device, each labelled
    by the benchmark span open at the gap's middle."""
    tot = {}
    for n, a, b, k in red.ops():
        if k != "parent":
            tot[n] = tot.get(n, 0.0) + (b - a)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(red), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[_label(red, (a + b) / 2), (b - a) * 1e-9]
                          for a, b in gaps]}
