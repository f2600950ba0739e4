"""What the training runners share: the mesh, Adam, and host copies."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

B1, B2, EPS = 0.9, 0.999, 1e-8


def data_mesh(devices, k: int) -> Mesh:
    """A one-axis ``data`` mesh over the first ``k`` devices."""
    if len(devices) < k:
        raise RuntimeError(f"the mix needs {k} devices, JAX sees "
                           f"{len(devices)}")
    return Mesh(np.array(devices[:k]), ("data",))


def replicate(tree, mesh: Mesh):
    return jax.device_put(jax.tree.map(jnp.asarray, tree),
                          NamedSharding(mesh, P()))


@jax.jit
def adam_update(params, grads, state, t, lr):
    """One Adam step on every leaf; ``state`` = (first, second) moments."""
    mom, vel = state
    mom = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, mom, grads)
    vel = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, vel, grads)
    params = jax.tree.map(
        lambda p, a, v: p - lr * (a / (1 - B1 ** t))
        / (jnp.sqrt(v / (1 - B2 ** t)) + EPS), params, mom, vel)
    return params, (mom, vel)


def first_gradient(state):
    """The gradient of step 1, from Adam's state after it: m1 / (1 - b1)."""
    return jax.tree.map(lambda a: np.asarray(a) / (1 - B1), state[0])


def to_host(tree):
    """A float64 numpy copy of a pytree of device arrays."""
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)
