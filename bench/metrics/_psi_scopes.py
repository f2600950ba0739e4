"""Device time in the latent map's own named scopes, ``psi2`` and
``psi1_c`` (``core/stats.py``), which lie inside ``map`` and which
``bench/marks.py`` does not attribute: read here from each op's
``op_name`` path in ``marks.of(ctx).where``.  A scope is one component of
the path, unwrapped from JAX's transformation marks as ``marks.scope``
unwraps them; the backward's paths hold ``transpose(``.  A program
without these scopes (or a trace without paths) reads nothing."""
from __future__ import annotations

from bench import marks, trace


def _in(path: str, name: str) -> bool:
    for part in path.split("/"):
        while marks.TRANSFORM.match(part):
            part = marks.TRANSFORM.match(part).group(1)
        if part == name:
            return True
    return False


def ms_per_iteration(ctx, name: str, backward: bool | None,
                     kernels: bool) -> float | None:
    """Milliseconds per iteration, averaged over the chips, in which an op
    of scope ``name`` ran: its forward (``backward`` False), its backward
    (True) or both (None); with ``kernels`` False, less the time in which
    a kernel or a collective ran (the scope's XLA time)."""
    red = marks.of(ctx)
    if red is None or not ctx["iterations"]:
        return None
    total = 0.0
    for dev, d in red.devices.items():
        mine = trace.union(
            (op[1], op[2]) for op, (_, path) in zip(d.ops, red.where[dev])
            if _in(path, name) and
            (backward is None or ("transpose(" in path) == backward))
        total += trace.length(mine)
        if not kernels:
            total -= trace.length(trace.intersect(mine, d.work()))
    if total <= 0.0:
        return None
    return 1e-6 * total / len(red.devices) / ctx["iterations"]
