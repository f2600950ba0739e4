"""Exact Map-Reduce training iterations, data in device memory.

One iteration is the program's exact value and gradient of the negative
bound over all n rows (``DistributedGP.make_value_and_grad``, the fused
Pallas map, the psum over the mix's ``data`` mesh and the float64 global
step), then one Adam step on the parameters, ended by
``block_until_ready``: the paper's iteration.  Regression trains
(hyp, z); the GPLVM trains (hyp, z, mu), its variances s held fixed.

Mix parameters: ``mesh`` (chips on the data axis), ``reduce_mode``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen.flight import flight_rows, flight_start
from bench.gen.usps import gplvm_start
from bench.traffic import _shared

METRIC = "exact_rows_per_s"


class Runner:
    def __init__(self, config: dict, mix: dict, seed: int, devices,
                 step=None):
        """``step``: the compiled step of another runner of the same cell,
        taken over (the control readings run many seeds in one process,
        and the GPLVM step is never in the persistent cache)."""
        from repro.core import DistributedGP

        self.latent = config["model"] == "gplvm"
        n, m, q, d = (config[k] for k in ("n", "m", "q", "d"))
        if self.latent:
            y, mu, s, hyp, z = gplvm_start(n, q, m, seed,
                                           config["kmeans_iters"])
        else:
            rows = flight_rows(n, seed)
            y, mu, s = rows["y"], rows["mu"], None
            hyp, z = flight_start(rows, m, seed)
        self.host = {"y": y, "mu": mu, "s": s}
        self.n = n
        self.rows_per_iteration = n
        self.lr = config["optimizer"]["lr"]
        mesh = _shared.data_mesh(devices, mix["mesh"])
        self.devices = list(mesh.devices.flat)
        eng = DistributedGP(mesh, latent=self.latent,
                            chunk_size=config["chunk_size"],
                            kernel_backend="pallas",
                            reduce_mode=mix["reduce_mode"])
        arrs = {"y": y, "mu": mu} if s is None else {"y": y, "mu": mu, "s": s}
        data, w = eng.put_data(**arrs)
        self.step = step or eng.make_value_and_grad(
            d, argnums=(0, 1, 2) if self.latent else (0, 1))
        params = _shared.replicate((hyp, z), mesh)
        if self.latent:
            params = params + (data["mu"],)
            self.fixed = (data["s"], data["y"], w)
        else:
            self.fixed = (data["mu"], None, data["y"], w)
        self.fixed += _shared.replicate(
            (jnp.ones((eng.n_shards,)), jnp.asarray(float(n))), mesh)
        self.params = params
        self.state = (jax.tree.map(jnp.zeros_like, params),) * 2
        self.t = 0
        self.start = self.params_host()

    def iteration(self) -> float:
        """One iteration; returns its loss (the negative bound)."""
        self.t += 1
        with jax.profiler.TraceAnnotation("iteration"):
            with jax.profiler.TraceAnnotation("value_and_grad"):
                loss, grads = self.step(*self.params, *self.fixed)
            with jax.profiler.TraceAnnotation("optimizer_update"):
                self.params, self.state = _shared.adam_update(
                    self.params, grads, self.state,
                    jnp.asarray(float(self.t)), self.lr)
            loss = float(loss)
            jax.block_until_ready(self.params)
        return loss

    def _cut(self, tree):
        tree = _shared.to_host(tree)
        return (*tree[:2], tree[2][:self.n]) if self.latent else tree

    def params_host(self):
        return self._cut(self.params)

    def first_gradient(self):
        return self._cut(_shared.first_gradient(self.state))

    def reference_feeds(self, steps: int) -> list[dict]:
        feed = {"y": self.host["y"], "x": None if self.latent
                else self.host["mu"], "s": self.host["s"],
                "w": np.ones(self.n), "scale": 1.0, "n": float(self.n)}
        return [feed] * steps

    def close(self):
        """Drop the program's device state."""
        self.params = self.state = self.fixed = self.step = None
