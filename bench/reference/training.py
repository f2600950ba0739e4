"""The reference training run: the negative bound, its gradient and plain
Adam, in the precision asked for (float64 for the reference, float32 for
the control), every matrix product at full precision.

A step's feed is a dict of host arrays: ``y``, ``x`` (inputs; the initial
means in the GPLVM, where the means are parameters), ``s`` (GPLVM only),
``w`` (row weights, 0 on padding), ``scale`` (a minibatch's
n_chunks / batch_chunks, else 1) and ``n`` (the bound's n).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bound import collapsed_bound
from .stats import summed_stats

B1, B2, EPS = 0.9, 0.999, 1e-8


@functools.partial(jax.jit, static_argnames=("d", "jitter", "block"))
def _neg_and_grad(params, y, x, s, w, scale, n, *, d, jitter, block):
    def neg(params):
        hyp, z = params[0], params[1]
        xin = params[2] if len(params) == 3 else x
        st = summed_stats(hyp, z, y, xin, s, w, block)
        st = {k: scale * v for k, v in st.items()}
        st["n"] = n
        return -collapsed_bound(hyp, z, st, d, jitter)

    return jax.value_and_grad(neg)(params)


@jax.jit
def _adam(params, grads, state, t, lr):
    mom, vel = state
    mom = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, mom, grads)
    vel = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, vel, grads)
    params = jax.tree.map(
        lambda p, a, v: p - lr * (a / (1 - B1 ** t))
        / (jnp.sqrt(v / (1 - B2 ** t)) + EPS), params, mom, vel)
    return params, (mom, vel)


def train(start, feeds, *, d: int, jitter: float, block: int, lr: float,
          dtype=np.float64, fault=None):
    """Adam from ``start`` (a tuple of numpy pytrees) over ``feeds``, one
    step each.  Returns ``{"losses", "grad1", "change"}``: each step's
    negative bound, the first gradient, and the parameters' change after
    the last step (numpy pytrees).  ``fault`` plants one of the faults the
    comparison must catch: ``"unchanged"`` (the update returns its state
    unchanged)."""
    def cast(a):
        return None if a is None else jnp.asarray(np.asarray(a, dtype))

    params = jax.tree.map(cast, start)
    state = (jax.tree.map(jnp.zeros_like, params),) * 2
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for t, f in enumerate(feeds, 1):
            loss, grads = _neg_and_grad(
                params, cast(f["y"]), cast(f["x"]), cast(f.get("s")),
                cast(f["w"]), cast(f["scale"]), cast(f["n"]), d=d,
                jitter=jitter, block=block)
            losses.append(float(loss))
            if grad1 is None:
                grad1 = jax.tree.map(np.asarray, grads)
            if fault != "unchanged":
                params, state = _adam(params, grads, state, cast(t), lr)
    change = jax.tree.map(lambda p, p0: np.asarray(p, np.float64) - p0,
                          params, start)
    return {"losses": losses, "grad1": grad1, "change": change}
