"""Minibatch (SVI) training streamed from host memory.

The rows sit in host RAM (an ``ArraySource``, as a user's loaded data
would).  One iteration is one ``DistributedGP.streamed_svi_value_and_grad``
step: the program samples ``batch_chunks`` chunks with the step's key,
assembles them on the host, stages them on the device and returns the
value and gradient of the reweighted negative bound; then one Adam step
on (hyp, z), ended by ``block_until_ready``.  The keys of the first three
steps are chosen so that their chunks differ.

Mix parameters: ``mesh``, ``batch_chunks``, ``blocks_per_chunk``, ``lr``
(Adam's rate: minibatch steps come some hundreds to a window, and at the
full-batch rate 2e-2 the flight fit left the range where the float32 map
statistics hold within ~100 steps, its bound non-finite after that).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen.flight import flight_rows, flight_start
from bench.traffic import _shared

METRIC = "svi_rows_per_s"


class Runner:
    def __init__(self, config: dict, mix: dict, seed: int, devices):
        from repro.core import DistributedGP
        from repro.data.stream import ArraySource

        if config["model"] != "sgpr":
            raise ValueError("svi_stream drives the regression model only")
        n, m, d = config["n"], config["m"], config["d"]
        self.rows = flight_rows(n, seed)
        hyp, z = flight_start(self.rows, m, seed)
        self.n, self.lr = n, mix["lr"]
        mesh = _shared.data_mesh(devices, mix["mesh"])
        self.devices = list(mesh.devices.flat)
        eng = DistributedGP(mesh, chunk_size=config["chunk_size"],
                            kernel_backend="pallas")
        self.stream = eng.put_data(stream=ArraySource(self.rows),
                                   blocks_per_chunk=mix["blocks_per_chunk"])
        self.batch = min(mix["batch_chunks"], self.stream.n_chunks)
        self.rows_per_iteration = self.batch * self.stream.chunk_rows
        self.step = eng.streamed_svi_value_and_grad(
            d=d, batch_chunks=mix["batch_chunks"])
        self.geometry = (mix["mesh"], config["chunk_size"],
                         self.stream.blocks_per_chunk)
        self.params = _shared.replicate((hyp, z), mesh)
        self.state = (jax.tree.map(jnp.zeros_like, self.params),) * 2
        self.base = jax.random.PRNGKey(seed)
        self.key_ids = self._disjoint_keys(3)
        self.t = 0
        self.start = self.params_host()

    def _chunks(self, key_id: int) -> np.ndarray:
        """The chunks the step with key ``key_id`` samples: a uniform
        subset without replacement, the first ``batch`` of a permutation
        of the chunk indices drawn from the key."""
        key = jax.random.fold_in(self.base, key_id)
        return np.asarray(jax.random.permutation(
            key, self.stream.n_chunks)[:self.batch])

    def _disjoint_keys(self, k: int) -> list[int]:
        ids, seen, j = [], set(), 0
        while len(ids) < k:
            c = set(self._chunks(j).tolist())
            if not c & seen:
                ids.append(j)
                seen |= c
            j += 1
        return ids

    def _key_id(self, t: int) -> int:
        return self.key_ids[t - 1] if t <= len(self.key_ids) else \
            self.key_ids[-1] + t - len(self.key_ids)

    def iteration(self) -> float:
        self.t += 1
        with jax.profiler.TraceAnnotation("iteration"):
            key = jax.random.fold_in(self.base, self._key_id(self.t))
            with jax.profiler.TraceAnnotation("value_and_grad"):
                loss, grads = self.step(*self.params, self.stream, key)
            with jax.profiler.TraceAnnotation("optimizer_update"):
                self.params, self.state = _shared.adam_update(
                    self.params, grads, self.state,
                    jnp.asarray(float(self.t)), self.lr)
            loss = float(loss)
            jax.block_until_ready(self.params)
        return loss

    def params_host(self):
        return _shared.to_host(self.params)

    def first_gradient(self):
        return _shared.to_host(_shared.first_gradient(self.state))

    def _chunk_rows(self, c: int) -> np.ndarray:
        """Row indices of chunk ``c``: for every shard, its local blocks
        [c * bpc, (c + 1) * bpc) in the padded shard-major layout, cut to
        the real rows."""
        shards, block, bpc = self.geometry
        per_shard = -(-self.n // (shards * block)) * block
        span = bpc * block
        idx = [np.arange(sh * per_shard + c * span,
                         min(sh * per_shard + (c + 1) * span,
                             (sh + 1) * per_shard))
               for sh in range(shards)]
        idx = np.concatenate(idx)
        return idx[idx < self.n]

    def reference_feeds(self, steps: int) -> list[dict]:
        feeds = []
        for t in range(1, steps + 1):
            idx = np.concatenate([self._chunk_rows(int(c))
                                  for c in self._chunks(self._key_id(t))])
            feeds.append({"y": self.rows["y"][idx], "x": self.rows["mu"][idx],
                          "s": None, "w": np.ones(idx.size),
                          "scale": self.stream.n_chunks / self.batch,
                          "n": float(self.n)})
        return feeds

    def close(self):
        self.params = self.state = self.step = self.stream = None
