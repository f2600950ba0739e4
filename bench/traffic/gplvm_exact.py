"""Exact Map-Reduce GPLVM iterations on MNIST-shaped images, in device
memory.

One iteration is the program's exact value and gradient of the negative
bound over all n rows (``DistributedGP(latent=True)``'s
``make_value_and_grad``: the fused psi2 kernel, psi1 and C, the psum over
the mix's ``data`` mesh and the float64 global step), then one Adam step
on (hyp, z, mu), the variances s held fixed, ended by
``block_until_ready``: ``exact.py``'s iteration, with rows from
``bench/gen/mnist.py``.

Mix parameters: ``mesh`` (chips on the data axis), ``reduce_mode``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.gen.mnist import gplvm_start
from bench.traffic import _shared, exact

METRIC = "exact_rows_per_s"


class Runner(exact.Runner):
    def __init__(self, config: dict, mix: dict, seed: int, devices,
                 step=None):
        """``step``: the compiled step of another runner of the same cell,
        taken over (``bench/control.py`` runs many seeds in one process)."""
        from repro.core import DistributedGP

        n, m, q, d = (config[k] for k in ("n", "m", "q", "d"))
        y, mu, s, hyp, z = gplvm_start(n, q, m, seed, config["kmeans_iters"])
        self.latent = True
        self.host = {"y": y, "mu": mu, "s": s}
        self.n = n
        self.rows_per_iteration = n
        self.lr = config["optimizer"]["lr"]
        mesh = _shared.data_mesh(devices, mix["mesh"])
        self.devices = list(mesh.devices.flat)
        eng = DistributedGP(mesh, latent=True,
                            chunk_size=config["chunk_size"],
                            kernel_backend="pallas",
                            reduce_mode=mix["reduce_mode"])
        data, w = eng.put_data(y=y, mu=mu, s=s)
        self.step = step or eng.make_value_and_grad(d, argnums=(0, 1, 2))
        params = _shared.replicate((hyp, z), mesh) + (data["mu"],)
        self.fixed = (data["s"], data["y"], w) + _shared.replicate(
            (jnp.ones((eng.n_shards,)), jnp.asarray(float(n))), mesh)
        self.params = params
        self.state = (jax.tree.map(jnp.zeros_like, params),) * 2
        self.t = 0
        self.start = self.params_host()
