"""Distributed Map-Reduce inference engine (paper §3.2) on a JAX mesh.

The paper's two global steps per iteration map onto one SPMD program:

  map    : every shard computes partial stats (A_k, B_k, C_k, D_k, KL_k)
           from its local (Y_k, mu_k, S_k) — zero communication, O(n_k m^2 q).
  reduce : one ``lax.psum`` over the data axes — O(m^2 + m d) bytes,
           independent of n (the paper's "constant time" global step).
  global : every chip evaluates the collapsed bound from the reduced stats
           (replicated O(m^3) — trivial, and it removes the central node).

Gradients come from ``jax.grad`` through the same program: the transpose of
a psum is replication, so the backward pass is also one constant-size
collective + shard-local work — exactly the paper's step-3 scatter of
(F, dF) to the end-point nodes.

Node failure (paper §5.2): a per-shard ``failure_mask`` zeroes a shard's
contribution inside the reduce.  ``failure_mode``:
  * "drop"    — paper-faithful: surviving partial sums used as-is (noisy
                gradient; the bound's n-terms keep the full n).
  * "rescale" — beyond-paper: surviving sums scaled by n/n_live, keeping the
                statistics approximately unbiased (see benchmarks/fig7).

Streaming memory model (``chunk_size``): with ``chunk_size=None`` each
shard's map materialises all of its n_k rows' intermediates at once — for
the GPLVM path that is the O(n_k m^2) (and transiently O(n_k m^2 q)) psi2
broadcast, so per-device *memory*, not compute, caps n.  Setting
``chunk_size=B`` makes the shard-local map a ``lax.scan`` over
``ceil(n_k / B)`` fixed-size row blocks (``stats.partial_stats_chunked``),
folding each block's Stats into a constant-size carry.  Peak live memory
per shard becomes

    O(B * (m + q + d))  [one block's intermediates]  +  O(m^2 + m d) [carry]

independent of n_k, while the reduce is unchanged: still ONE psum of
O(m^2 + m d) bytes after the scan finishes (map stays zero-communication,
reduce stays constant-size — exactly the paper's cost model, now with a
bounded map footprint).  ``put_data`` pads n up to a multiple of
``n_shards * chunk_size`` so every scan step is shape-static; padded rows
carry zero weight and contribute nothing.

Minibatch-stochastic bound (``batch_blocks``, Hensman-style SVI): the same
factorisation that lets blocks stream also lets them be *subsampled* —
each shard visits ``batch_blocks`` random blocks per step and scales its
partial Stats by ``n_local_blocks / batch_blocks``, making per-step map
*compute* (not just memory) O(batch_blocks * chunk_size), independent of
n.  Shards sample independently (the step key is folded with the shard
index), the psum is unchanged, and the reweighted reduced Stats are
unbiased estimates of the exact ones.  See docs/training.md for the
derivation, which bound terms inherit exact unbiasedness, and tuning.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .bound import DEFAULT_JITTER, collapsed_bound
from .stats import Stats, fold_stats, partial_stats_chunked

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the bodies psum by
    hand and return replicated values the checker cannot prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


Array = jax.Array


def _flat_shard_index(mesh: Mesh, axis_names: Sequence[str]) -> Array:
    """Flattened shard index along ``axis_names`` (row-major)."""
    idx = jnp.zeros((), jnp.int32)
    for ax in axis_names:
        idx = idx * mesh.shape[ax] + lax.axis_index(ax)
    return idx


def num_shards(mesh: Mesh, axis_names: Sequence[str]) -> int:
    out = 1
    for ax in axis_names:
        out *= mesh.shape[ax]
    return out


def pad_and_shard(arrs: dict, n_shards: int, block: int | None = None):
    """Pad leading dim to a multiple of n_shards; return arrays + weight vec.

    Args:
      arrs: dict of host arrays, each (n, ...) with a shared leading dim —
        e.g. ``{"y": (n, d), "mu": (n, q), "s": (n, q)}``.  Keys named
        ``"s"``/``"S"`` (q(X) variances) are padded with 1s (log-safe);
        everything else with 0s.
      n_shards: number of data shards the mesh provides; the padded n is the
        next multiple of ``n_shards`` (times ``block`` if set).
      block: the streaming chunk size (``chunk_size`` on the engines), or
        None.  When set, pads to a multiple of ``n_shards * block`` instead,
        so each shard holds a whole number of blocks and every ``lax.scan``
        step in the chunked map — and every SVI block sample — is
        shape-static.

    Returns ``(padded dict, weights)`` where ``weights`` is (n_padded,) —
    1.0 on real rows, 0.0 on padding — so padding contributes nothing to any
    statistic (see ``stats.partial_stats``).  Runs on host (numpy in, numpy
    out) before device_put.

    The padded n is always at least one full multiple: n < n_shards·block
    (including n = 0) pads up to ``n_shards * block`` rather than producing
    shard-empty (or zero-length) arrays that the shard_map programs cannot
    split.  ``unpad`` inverts the row padding.
    """
    import numpy as np

    from ..data.stream import padded_rows

    mult = n_shards * (block or 1)
    n = next(iter(arrs.values())).shape[0]
    pad = padded_rows(n, mult) - n
    out = {}
    for k, a in arrs.items():
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        # Pad q(X) variances with 1s (log-safe); everything else with 0s.
        cval = 1.0 if k in ("s", "S") else 0.0
        out[k] = np.pad(np.asarray(a), widths, constant_values=cval)
    w = np.concatenate([np.ones((n,), np.float64), np.zeros((pad,), np.float64)])
    return out, w


def unpad(arrs, n: int):
    """Strip the row padding ``pad_and_shard`` added: slice every array in
    ``arrs`` (a dict, or one array) back to its first ``n`` rows — the exact
    inverse of the padding, so ``unpad(pad_and_shard(x)[0], n) == x``."""
    if isinstance(arrs, dict):
        return {k: a[:n] for k, a in arrs.items()}
    return arrs[:n]


class DistributedGP:
    """Builds jitted distributed bound/grad programs for SGPR and GPLVM."""

    def __init__(
        self,
        mesh: Mesh,
        data_axes: Sequence[str] = ("data",),
        latent: bool = False,
        failure_mode: str = "drop",
        psi2_fn=None,
        reg_stats_fn=None,
        chunk_size: int | None = None,
        kernel_backend: str = "xla",
        batch_blocks: int | None = None,
        kernel=None,
        reduce_mode: str = "serial",
    ):
        """``kernel``: the covariance expression (``core.covariance``;
        None = SE-ARD).  Threaded through the shard-local map and the
        replicated global bound; the Pallas backend keeps its fused fast
        path for the SE-ARD default and falls back to the XLA map for
        other expressions (the ops-layer shims assert nothing — parity is
        covered by tests/test_kernel_zoo.py).

        ``chunk_size``: if set, each shard's map streams its rows in
        blocks of this many points (see the module docstring's streaming
        memory model); ``None`` (default) keeps the monolithic
        all-rows-at-once map.

        ``kernel_backend``: "xla" (default) keeps the monolithic jnp map;
        "pallas" routes the map's hot accumulation through the fused Pallas
        kernels — ``kernels.reg_stats`` on the regression path and
        ``kernels.psi_stats`` on the latent path — so the per-block kernel
        slab stays in VMEM.  Explicit ``psi2_fn``/``reg_stats_fn`` hooks
        override the backend's choice.

        ``batch_blocks``: if set (requires ``chunk_size``), switches the map
        to the minibatch-stochastic (SVI) bound: *each shard* samples
        ``batch_blocks`` of its local row blocks per step — with its own
        fold of the step key, so shards sample independently — and scales
        its partial Stats by ``n_local_blocks / batch_blocks`` before the
        psum.  Per-step map cost becomes O(batch_blocks * chunk_size) per
        shard, independent of the shard's row count; the reduce is unchanged
        (one O(m²+md) psum).  The programs returned by :meth:`bound_fn` and
        :meth:`make_value_and_grad` then take one extra trailing argument: a
        ``jax.random.PRNGKey`` (uint32 (2,)), fresh per step.  Default None
        = exact bound (every block scanned every step).

        ``reduce_mode``: how the bound/grad programs reduce the map's
        Stats across shards (requires ``chunk_size`` for the non-serial
        modes).

          * ``"serial"`` (default) — the paper-shaped structure: the whole
            shard-local scan finishes, then ONE constant-size psum.  The
            collective serialises after the map.
          * ``"overlap"`` — the overlapped reduce: each scanned block's
            constant-size Stats contribution is psummed *inside* the scan
            behind a double buffer, so block t's collective has no data
            dependence on block t+1's compute and rides behind it (the
            carry accumulates already-reduced Stats).  Bounds and grads
            match ``"serial"`` to float-reassociation (f64) tolerance —
            the cross-shard/cross-block sums associate per block instead
            of per pass, so bitwise equality to the serial path is a
            mathematical impossibility, not an implementation gap.
          * ``"overlap_eager"`` — validation mode: the same per-block
            reduce without the double buffer (block t reduced in step t).
            Bitwise-identical Stats/bound/grads to ``"overlap"`` (the
            fold order over blocks is the same — asserted in
            tests/_dist_worker.py), useful to isolate scheduling effects.

        The exact-stats programs (:meth:`reduced_stats`,
        :meth:`update_stats_fn`, the streamed ingestion family) always
        use the serial reduce: their bitwise streamed==staged contracts
        are defined against the single-psum association."""
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if batch_blocks is not None:
            if chunk_size is None:
                raise ValueError(
                    "batch_blocks (SVI mode) requires chunk_size: the "
                    "minibatch is a subset of the streaming row blocks")
            if batch_blocks < 1:
                raise ValueError(
                    f"batch_blocks must be >= 1, got {batch_blocks}")
        if kernel_backend not in ("xla", "pallas"):
            raise ValueError(
                f"kernel_backend must be 'xla' or 'pallas', got {kernel_backend!r}")
        if reduce_mode not in ("serial", "overlap", "overlap_eager"):
            raise ValueError(
                "reduce_mode must be 'serial', 'overlap' or 'overlap_eager'"
                f", got {reduce_mode!r}")
        if reduce_mode != "serial" and chunk_size is None:
            raise ValueError(
                "reduce_mode='overlap' requires chunk_size: the per-block "
                "collective needs scan blocks to hide behind")
        from .covariance import as_kernel
        self.kernel = as_kernel(kernel)
        if kernel_backend == "pallas":
            from ..kernels.psi_stats import psi2_fn_for_engine
            from ..kernels.reg_stats import reg_stats_fn_for_engine
            psi2_fn = psi2_fn or psi2_fn_for_engine(kernel=self.kernel)
            reg_stats_fn = reg_stats_fn or reg_stats_fn_for_engine(
                kernel=self.kernel)
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.latent = latent
        self.failure_mode = failure_mode
        self.psi2_fn = psi2_fn
        self.reg_stats_fn = reg_stats_fn
        self.kernel_backend = kernel_backend
        self.chunk_size = chunk_size
        self.batch_blocks = batch_blocks
        self.reduce_mode = reduce_mode
        self.n_shards = num_shards(mesh, self.data_axes)
        self._data_spec = P(self.data_axes)
        self._rep_spec = P()
        self._stats_prog = None   # cached reduced_stats program (serving)
        self._stream_cache: dict = {}   # streamed-ingestion programs

    # -- sharding helpers ---------------------------------------------------
    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._data_spec)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._rep_spec)

    def put_data(self, stream=None, blocks_per_chunk: int = 1, **arrs):
        """Stage host data for the SPMD programs.

        In-memory mode (``put_data(y=..., mu=..., ...)``): pad + shard the
        arrays onto the mesh; returns ``(dict, weights)`` — the whole
        padded dataset is resident on device.

        Streaming mode (``put_data(stream=source)``): no staging happens —
        returns a ``data.stream.BlockStream`` over the source (a dict of
        host arrays, a ``MemmapSource``/``SyntheticSource``, or any
        ``(n, fields, read)`` object) cut into fixed-shape shard-major
        chunks of ``blocks_per_chunk`` scan blocks per shard.  Feed it to
        :meth:`streamed_stats` / :meth:`streamed_value_and_grad` /
        :meth:`streamed_predictive_state`, which hold O(chunk) rows on
        device at a time and reproduce the in-memory programs bitwise
        (Stats/bound) or to f64 tolerance (grads).  Requires
        ``chunk_size`` — the streaming block geometry is the scan-block
        geometry.
        """
        if stream is not None:
            if arrs:
                raise ValueError(
                    "put_data takes either stream=... or in-memory arrays, "
                    "not both")
            return self.open_stream(stream, blocks_per_chunk=blocks_per_chunk)
        padded, w = pad_and_shard(arrs, self.n_shards, block=self.chunk_size)
        sh = self.data_sharding()
        out = {k: jax.device_put(jnp.asarray(v), sh) for k, v in padded.items()}
        wdev = jax.device_put(jnp.asarray(w), sh)
        return out, wdev

    def open_stream(self, source, blocks_per_chunk: int = 1):
        """Wrap a host data source in a ``BlockStream`` with this engine's
        shard/block geometry (``n_shards`` shards, ``chunk_size`` rows per
        scan block) — the layout under which streamed ingestion is bitwise
        equal to :meth:`put_data` + the in-device scan."""
        from ..data.stream import BlockStream

        if self.chunk_size is None:
            raise ValueError(
                "streaming ingestion requires chunk_size: the host chunks "
                "are multiples of the in-device scan block")
        if isinstance(source, BlockStream):
            if (source.n_shards != self.n_shards
                    or source.block_size != self.chunk_size):
                raise ValueError(
                    f"stream geometry ({source.n_shards} shards × "
                    f"{source.block_size}-row blocks) does not match the "
                    f"engine ({self.n_shards} × {self.chunk_size}) — open "
                    "the stream through this engine")
            return source
        return BlockStream(source, n_shards=self.n_shards,
                           block_size=self.chunk_size,
                           blocks_per_chunk=blocks_per_chunk)

    # -- the SPMD program ---------------------------------------------------
    def _psum_stats(self, st: Stats) -> Stats:
        """Per-leaf constant-size cross-shard sum (the paper's reduce)."""
        return Stats(*(lax.psum(t, self.data_axes) for t in st))

    def _bound(self, hyp, z, st: Stats, d: int):
        """The collapsed bound from reduced Stats.  When D comes from the
        fused psi2 kernel, an indefinite ``Bmat`` raises instead of
        returning NaN (``bound.collapsed_bound(guard_definite=True)``)."""
        return collapsed_bound(
            hyp, z, st, d, kernel=self.kernel,
            guard_definite=self.latent and self.kernel_backend == "pallas")

    def _local_stats(self, hyp, z, y, mu, s, w, key=None, exact=False,
                     block_reduce_fn=None, reduce_buffered=True) -> Stats:
        """Shard-local map: monolithic (chunk_size=None), streamed, or —
        with ``batch_blocks`` set and a per-shard ``key`` — SVI-sampled.
        ``exact=True`` forces the full scan regardless of ``batch_blocks``
        (the posterior/prediction path).  ``block_reduce_fn`` switches to
        the overlapped per-block reduce — the returned Stats are then
        already globally reduced."""
        return partial_stats_chunked(
            hyp, z, y, mu, s,
            weights=w, latent=self.latent, psi2_fn=self.psi2_fn,
            reg_stats_fn=self.reg_stats_fn, block_size=self.chunk_size,
            batch_blocks=None if exact else self.batch_blocks, key=key,
            kernel=self.kernel, force_scan=True,
            block_reduce_fn=block_reduce_fn, reduce_buffered=reduce_buffered,
        )

    def _shard_bound(self, hyp, z, y, mu, s, w, fmask, n_full, d, key=None):
        """Runs per-shard under shard_map. Returns the (replicated) bound."""
        idx = _flat_shard_index(self.mesh, self.data_axes)
        alive = fmask[idx]
        w = w * alive

        if key is not None:
            # Per-shard sampling: every shard folds its flat index into the
            # (replicated) step key, so shards draw independent block
            # subsets.  Independence keeps the summed estimator unbiased:
            # E[psum of per-shard reweighted Stats] = psum of exact Stats.
            key = jax.random.fold_in(key, idx)
        if self.reduce_mode == "serial":
            st = self._local_stats(hyp, z, y, mu, s, w, key=key)
            # --- the reduce: one constant-size collective after the map ----
            st = self._psum_stats(st)
        else:
            # Overlapped reduce: each block's Stats contribution is psummed
            # inside the map scan (double-buffered in "overlap" so block
            # t's collective rides behind block t+1's compute); the scan
            # returns already-reduced Stats and no post-map collective
            # remains on the critical path.
            st = self._local_stats(
                hyp, z, y, mu, s, w, key=key,
                block_reduce_fn=self._psum_stats,
                reduce_buffered=(self.reduce_mode == "overlap"))

        if self.failure_mode == "rescale":
            if key is None:
                n_live = st.n
            else:
                # SVI: st.n is a stochastic reweighted count — dividing by
                # it would make a biased ratio estimator that conflates
                # sampling noise with node failure.  Rescale by the
                # deterministic pre-sampling live count instead (one cheap
                # extra scalar psum), which preserves unbiasedness: a
                # constant per-step multiplier commutes with E[.].
                n_live = lax.psum(jnp.sum(w), self.data_axes)
            live_frac = n_live / n_full
            st = Stats(
                A=st.A / live_frac, B=st.B / live_frac, C=st.C / live_frac,
                D=st.D / live_frac, KL=st.KL / live_frac, n=n_full,
            )
        else:  # "drop" (paper) — keep sums as-is, n-terms use the full n
            st = st._replace(n=n_full)
        return self._bound(hyp, z, st, d)

    def bound_fn(self, d: int):
        """Replicated-output distributed bound.

        Signature: ``(hyp, z, y, mu, s, w, fmask, n_full) -> ()`` — plus a
        trailing per-step ``key`` when the engine was built with
        ``batch_blocks`` (SVI mode).
        """
        specs = [
            self._rep_spec,   # hyp (pytree of scalars/vectors)
            self._rep_spec,   # z
            self._data_spec,  # y
            self._data_spec,  # mu
            self._data_spec,  # s (None for regression: empty pytree)
            self._data_spec,  # w
            self._rep_spec,   # fmask
            self._rep_spec,   # n_full
        ]
        if self.batch_blocks is not None:
            specs.append(self._rep_spec)  # step key (folded per shard inside)

            def body(hyp, z, y, mu, s, w, fmask, n_full, key):
                return self._shard_bound(hyp, z, y, mu, s, w, fmask, n_full,
                                         d=d, key=key)
        else:
            body = functools.partial(self._shard_bound, d=d)
        return shard_map(body, mesh=self.mesh, in_specs=tuple(specs),
                         out_specs=self._rep_spec)

    def make_value_and_grad(self, d: int, argnums=(0, 1)):
        """Jitted (value, grad) of the NEGATIVE bound wrt chosen args.

        argnums indexes (hyp, z, mu, s): for SGPR use (0, 1); for GPLVM add
        mu and s — their gradients stay sharded with the data (the paper's
        local-parameter optimisation, no extra communication).

        The returned step is ``step(hyp, z, mu, s, y, w, fmask, n_full)``;
        in SVI mode (``batch_blocks`` set) it takes one extra trailing
        argument, a fresh ``jax.random.PRNGKey`` per step, and returns an
        unbiased stochastic estimate instead of the exact value/grad.
        """
        bound = self.bound_fn(d)

        if self.batch_blocks is not None:
            def neg_svi(hyp, z, mu, s, y, w, fmask, n_full, key):
                return -bound(hyp, z, y, mu, s, w, fmask, n_full, key)

            return jax.jit(jax.value_and_grad(neg_svi, argnums=argnums))

        def neg(hyp, z, mu, s, y, w, fmask, n_full):
            return -bound(hyp, z, y, mu, s, w, fmask, n_full)

        return jax.jit(jax.value_and_grad(neg, argnums=argnums))

    def reduced_stats(self, d: int):
        """Jitted program returning the globally-reduced Stats (for
        q(u)/predict).  Always the exact scan — posterior/prediction should
        see every point even when training ran in SVI mode."""

        def _stats(hyp, z, y, mu, s, w, fmask):
            idx = _flat_shard_index(self.mesh, self.data_axes)
            w = w * fmask[idx]
            st = self._local_stats(hyp, z, y, mu, s, w, exact=True)
            return Stats(*(lax.psum(t, self.data_axes) for t in st))

        f = shard_map(
            _stats,
            mesh=self.mesh,
            in_specs=(
                self._rep_spec, self._rep_spec, self._data_spec,
                self._data_spec, self._data_spec, self._data_spec, self._rep_spec,
            ),
            out_specs=self._rep_spec,
        )
        return jax.jit(f)

    # -- streaming ingestion (host-fed chunk loop) --------------------------
    #
    # The in-memory programs stage the whole padded dataset on device; the
    # streamed ones below hold ONE fixed-shape chunk (blocks_per_chunk scan
    # blocks per shard) at a time, threading a *sharded* Stats carry — every
    # leaf gains a leading (n_shards,) axis, spec P(data_axes) — through a
    # per-chunk fold program that contains NO collective (jaxpr-asserted in
    # tests/_dist_worker.py).  Because chunk assembly is shard-major
    # (data.stream.BlockStream) and the carry threads INTO the chunked
    # scan's own accumulator (stats.partial_stats_chunked(init=...)), each
    # shard performs the identical float-add sequence over the identical
    # block partition as the in-memory scan, and ONE final psum — the same
    # collective reduced_stats runs — collapses the carry.  Streamed Stats
    # and bound are therefore bitwise equal to the staged path, not merely
    # close (tests/test_stream_ingest.py); only gradients (recovered by a
    # second pass through the stats cotangent) carry float-reassociation
    # error at f64 tolerance.  Host + device residency stays O(chunk) in n.

    def _stream_progs(self, has_s: bool):
        """Build (once per s-structure) the jitted per-chunk fold, final
        reduce, and chunk-cotangent programs."""
        cache_key = ("progs", has_s)
        progs = self._stream_cache.get(cache_key)
        if progs is not None:
            return progs

        def _local(hyp, z, y, mu, s, w, init=None):
            return partial_stats_chunked(
                hyp, z, y, mu, s, weights=w, latent=self.latent,
                psi2_fn=self.psi2_fn, reg_stats_fn=self.reg_stats_fn,
                block_size=self.chunk_size, kernel=self.kernel, init=init,
                force_scan=True)

        def _fold(carry, hyp, z, y, mu, s, w, fmask):
            idx = _flat_shard_index(self.mesh, self.data_axes)
            w = w * fmask[idx]
            init = Stats(*(jnp.squeeze(t, 0) for t in carry))
            st = _local(hyp, z, y, mu, s, w, init=init)
            return Stats(*(t[None] for t in st))

        def _reduce(carry):
            st = Stats(*(jnp.squeeze(t, 0) for t in carry))
            return Stats(*(lax.psum(t, self.data_axes) for t in st))

        def _chunk_ip(hyp, z, y, mu, s, w, fmask, ct):
            # <this chunk's reduced Stats, cotangent ct> — pass 2 of the
            # streamed gradient differentiates this wrt (hyp, z).
            idx = _flat_shard_index(self.mesh, self.data_axes)
            w = w * fmask[idx]
            st = _local(hyp, z, y, mu, s, w)
            ip = sum(jnp.vdot(a, b) for a, b in zip(st, ct))
            return lax.psum(ip, self.data_axes)

        data, rep = self._data_spec, self._rep_spec
        fold = jax.jit(shard_map(
            _fold, mesh=self.mesh,
            in_specs=(data, rep, rep, data, data, data, data, rep),
            out_specs=data))
        reduce_ = jax.jit(shard_map(
            _reduce, mesh=self.mesh, in_specs=(data,), out_specs=rep))
        chunk_vg = jax.jit(jax.value_and_grad(shard_map(
            _chunk_ip, mesh=self.mesh,
            in_specs=(rep, rep, data, data, data, data, rep, rep),
            out_specs=rep), argnums=(0, 1)))
        progs = {"fold": fold, "reduce": reduce_, "chunk_vg": chunk_vg}
        self._stream_cache[cache_key] = progs
        return progs

    def _init_stream_carry(self, stream, hyp, z) -> Stats:
        """Zero sharded carry with the exact leaf shapes/dtypes one chunk's
        local stats produce (abstract eval — backend/kernel agnostic).
        The eval_shape re-traces the whole chunked map, so the resulting
        leaf structure is cached per (geometry, hyp/z structure) — carry
        init must stay cheap relative to one chunk's fold."""
        rows = stream.shard_chunk_rows
        key = ("carry", rows,
               tuple((k, tuple(v), str(jnp.dtype(stream.field_dtype(k))))
                     for k, v in sorted(stream.fields.items())),
               tuple(jnp.shape(t) for t in jax.tree.leaves((hyp, z))))
        shapes = self._stream_cache.get(key)
        if shapes is None:
            sds = {k: jax.ShapeDtypeStruct((rows,) + tuple(tr),
                                           jnp.dtype(stream.field_dtype(k)))
                   for k, tr in stream.fields.items()}
            wsd = jax.ShapeDtypeStruct((rows,), jnp.float64)

            def f(y, mu, s, w):
                return partial_stats_chunked(
                    hyp, z, y, mu, s, weights=w, latent=self.latent,
                    psi2_fn=self.psi2_fn, reg_stats_fn=self.reg_stats_fn,
                    block_size=self.chunk_size, kernel=self.kernel,
                    force_scan=True)

            shapes = jax.eval_shape(f, sds["y"], sds["mu"], sds.get("s"),
                                    wsd)
            self._stream_cache[key] = shapes
        carry = Stats(*(jnp.zeros((self.n_shards,) + t.shape, t.dtype)
                        for t in shapes))
        return jax.device_put(carry, self.data_sharding())

    def _stage_stream(self, stream, prefetch_depth: int, indices=None):
        """Prefetched iterator of device-staged ``(arrays, weights)`` chunks
        — chunk i+1's host assembly + H2D overlaps compute on chunk i."""
        from ..data.stream import prefetch, stage_to_device

        return prefetch(stream.chunks(indices),
                        stage_to_device(self.data_sharding()),
                        depth=prefetch_depth)

    def _stream_carry(self, hyp, z, stream, fmask, prefetch_depth: int):
        """Fold every chunk into the sharded carry (no collective yet)."""
        progs = self._stream_progs(has_s="s" in stream.fields)
        carry = self._init_stream_carry(stream, hyp, z)
        for arrs, w in self._stage_stream(stream, prefetch_depth):
            carry = progs["fold"](carry, hyp, z, arrs["y"], arrs["mu"],
                                  arrs.get("s"), w, fmask)
        return carry

    def streamed_stats(self, hyp, z, stream, fmask=None,
                       prefetch_depth: int = 2) -> Stats:
        """Exact reduced Stats from a host stream — bitwise equal to
        :meth:`reduced_stats` over the same (staged) data, with device
        residency O(chunk) instead of O(n).  ``stream`` is anything
        :meth:`open_stream` accepts."""
        stream = self.open_stream(stream)
        if fmask is None:
            fmask = jnp.ones((self.n_shards,))
        carry = self._stream_carry(hyp, z, stream, fmask, prefetch_depth)
        return self._stream_progs(has_s="s" in stream.fields)["reduce"](carry)

    def _collapse_prog(self, d: int):
        """Jitted (replicated) stats -> NEGATIVE bound with this engine's
        failure-mode n-handling — the same global math ``_shard_bound``
        runs after its psum, applied to already-reduced stats."""
        cache_key = ("collapse", d)
        prog = self._stream_cache.get(cache_key)
        if prog is not None:
            return prog

        def neg(hyp, z, st, n_full):
            if self.failure_mode == "rescale":
                live_frac = st.n / n_full
                st = Stats(A=st.A / live_frac, B=st.B / live_frac,
                           C=st.C / live_frac, D=st.D / live_frac,
                           KL=st.KL / live_frac, n=n_full)
            else:
                st = st._replace(n=n_full)
            return -self._bound(hyp, z, st, d)

        prog = {
            "neg": jax.jit(neg),
            "vg": jax.jit(jax.value_and_grad(neg, argnums=(0, 1, 2))),
        }
        self._stream_cache[cache_key] = prog
        return prog

    def _bound_from_carry_prog(self, d: int):
        """Mesh program: sharded carry -> psum -> failure-mode n-handling ->
        replicated bound.  Structured exactly like ``_shard_bound``'s
        post-map tail (the psum feeding the global math inside one
        shard_map) so the streamed bound compiles to the same float
        sequence as the in-memory one — this is what keeps the *bound*
        bitwise, not just the Stats."""
        cache_key = ("bound_carry", d)
        prog = self._stream_cache.get(cache_key)
        if prog is not None:
            return prog

        def body(carry, hyp, z, n_full):
            st = Stats(*(jnp.squeeze(t, 0) for t in carry))
            st = Stats(*(lax.psum(t, self.data_axes) for t in st))
            if self.failure_mode == "rescale":
                live_frac = st.n / n_full
                st = Stats(A=st.A / live_frac, B=st.B / live_frac,
                           C=st.C / live_frac, D=st.D / live_frac,
                           KL=st.KL / live_frac, n=n_full)
            else:
                st = st._replace(n=n_full)
            return self._bound(hyp, z, st, d)

        # NOT jitted: ``bound_fn`` hands back a bare shard_map, whose
        # op-by-op dispatch rounds like the eager path — jitting this tail
        # fuses the global math differently (≈1 ulp) and breaks the
        # bitwise-bound contract with the in-memory program.
        prog = shard_map(
            body, mesh=self.mesh,
            in_specs=(self._data_spec, self._rep_spec, self._rep_spec,
                      self._rep_spec),
            out_specs=self._rep_spec)
        self._stream_cache[cache_key] = prog
        return prog

    def streamed_bound(self, hyp, z, stream, d: int, fmask=None,
                       n_full=None, prefetch_depth: int = 2):
        """The distributed bound from a host stream — bitwise equal to
        :meth:`bound_fn` on the staged data (same chunk-folded Stats
        carry, same in-mesh psum + collapse tail)."""
        stream = self.open_stream(stream)
        if fmask is None:
            fmask = jnp.ones((self.n_shards,))
        n_full = float(stream.n) if n_full is None else n_full
        carry = self._stream_carry(hyp, z, stream, fmask, prefetch_depth)
        return self._bound_from_carry_prog(d)(carry, hyp, z, n_full)

    def streamed_value_and_grad(self, d: int, argnums=(0, 1)):
        """Streamed (value, grad) of the NEGATIVE bound wrt (hyp, z) —
        the exact two-pass gradient.

        Pass 1 streams the chunks once to build the reduced Stats S
        (bitwise the in-memory ones); the cotangent dS of the collapsed
        bound wrt S is one replicated O(m³) value_and_grad.  Pass 2
        streams the chunks again, accumulating the (hyp, z) gradient of
        ``<chunk stats, dS>`` per chunk — the chain rule through the
        w-linear Stats, so the total equals the in-memory
        :meth:`make_value_and_grad` up to float re-association (f64
        tolerance), at O(chunk) residency and two passes over the data.
        (For per-step training at scale prefer
        :meth:`streamed_svi_value_and_grad` — one sampled pass.)

        Returns ``step(hyp, z, stream, fmask=None, n_full=None,
        prefetch_depth=2) -> (val, grads)`` with ``grads`` ordered by
        ``argnums`` (subset of (0, 1): streamed mu/s gradients would be
        n-sized, which streaming exists to avoid).
        """
        single = not isinstance(argnums, (tuple, list))
        argnums = (argnums,) if single else tuple(argnums)
        if not set(argnums) <= {0, 1}:
            raise ValueError(
                "streamed gradients support argnums ⊆ (0, 1) (hyp, z): "
                "mu/s gradients are data-sized — stage those shards in "
                f"memory instead (got {argnums})")

        def step(hyp, z, stream, fmask=None, n_full=None,
                 prefetch_depth: int = 2):
            stream = self.open_stream(stream)
            if fmask is None:
                fmask = jnp.ones((self.n_shards,))
            n_full = float(stream.n) if n_full is None else n_full
            st = self.streamed_stats(hyp, z, stream, fmask=fmask,
                                     prefetch_depth=prefetch_depth)
            val, (g_hyp, g_z, ct) = self._collapse_prog(d)["vg"](
                hyp, z, st, n_full)
            progs = self._stream_progs(has_s="s" in stream.fields)
            for arrs, w in self._stage_stream(stream, prefetch_depth):
                _, (gh, gz) = progs["chunk_vg"](
                    hyp, z, arrs["y"], arrs["mu"], arrs.get("s"), w,
                    fmask, ct)
                g_hyp = jax.tree.map(jnp.add, g_hyp, gh)
                g_z = g_z + gz
            grads = tuple((g_hyp, g_z)[a] for a in argnums)
            return val, (grads[0] if single else grads)

        return step

    def streamed_svi_value_and_grad(self, d: int, batch_chunks: int,
                                    argnums=(0, 1)):
        """Minibatch-stochastic streamed step: sample ``batch_chunks`` of
        the stream's chunks per step (host-side, without replacement),
        stage only those, and return an unbiased (value, grad) of the
        NEGATIVE bound — one pass over O(batch_chunks · chunk) rows per
        step, independent of n.

        The sampling unit is the *chunk* (every shard visits the same
        chunk indices — the chunks partition the rows, so reweighting by
        ``n_chunks / batch_chunks`` is unbiased exactly as the in-memory
        per-shard block sampling is; the estimators differ only in their
        correlation structure).  Requires ``failure_mode="drop"`` — the
        rescale mode's deterministic pre-sampling live count would need a
        full pass over the stream.

        Returns ``step(hyp, z, stream, key, fmask=None, n_full=None) ->
        (val, grads)``; ``key`` is a fresh PRNGKey per optimiser step.
        """
        import numpy as np

        from .stats import sample_block_indices

        if isinstance(argnums, (tuple, list)):
            argnums = tuple(argnums)
        check = argnums if isinstance(argnums, tuple) else (argnums,)
        if not set(check) <= {0, 1}:
            raise ValueError(
                f"streamed gradients support argnums ⊆ (0, 1), got {argnums}")
        if batch_chunks < 1:
            raise ValueError(
                f"batch_chunks must be >= 1, got {batch_chunks}")
        if self.failure_mode == "rescale":
            raise NotImplementedError(
                "streamed SVI supports failure_mode='drop' only: rescale "
                "needs the deterministic live count, a full data pass")

        cache_key = ("svi", d, argnums)
        prog = self._stream_cache.get(cache_key)
        if prog is None:
            def _neg(hyp, z, y, mu, s, w, fmask, n_full, scale):
                # Local shapes (B, rows_per_shard_per_chunk, ...): flatten
                # the staged chunks back to contiguous rows, exact-scan
                # them, reweight — every Stats field is a per-point sum.
                idx = _flat_shard_index(self.mesh, self.data_axes)
                w = w * fmask[idx]

                def flat(a):
                    return a.reshape((a.shape[0] * a.shape[1],)
                                     + a.shape[2:])

                st = partial_stats_chunked(
                    hyp, z, flat(y), flat(mu),
                    None if s is None else flat(s), weights=flat(w),
                    latent=self.latent, psi2_fn=self.psi2_fn,
                    reg_stats_fn=self.reg_stats_fn,
                    block_size=self.chunk_size, kernel=self.kernel,
                    force_scan=True)
                st = st.scale(scale)
                st = Stats(*(lax.psum(t, self.data_axes) for t in st))
                st = st._replace(n=n_full)   # drop-mode n handling
                return -self._bound(hyp, z, st, d)

            stk = P(None, self.data_axes)
            rep = self._rep_spec
            prog = jax.jit(jax.value_and_grad(shard_map(
                _neg, mesh=self.mesh,
                in_specs=(rep, rep, stk, stk, stk, stk, rep, rep, rep),
                out_specs=rep), argnums=argnums))
            self._stream_cache[cache_key] = prog

        stacked_sharding = NamedSharding(self.mesh, P(None, self.data_axes))

        def step(hyp, z, stream, key, fmask=None, n_full=None):
            stream = self.open_stream(stream)
            if fmask is None:
                fmask = jnp.ones((self.n_shards,))
            n_full = float(stream.n) if n_full is None else n_full
            nc = stream.n_chunks
            B = min(batch_chunks, nc)
            # Host spans, for a profiler trace to put the device's idle
            # time down to: sampling, assembly (``BlockStream.chunk``),
            # host-to-device staging, dispatch.
            with TraceAnnotation("svi_sample"):
                if B < nc:
                    idxs = np.asarray(sample_block_indices(key, nc, B))
                else:
                    idxs = np.arange(nc)
            chunks = [stream.chunk(int(c)) for c in idxs]
            with TraceAnnotation("svi_h2d") as span:
                arrs = {k: jax.device_put(
                            jnp.asarray(np.stack([c[0][k] for c in chunks])),
                            stacked_sharding)
                        for k in stream.fields}
                w = jax.device_put(
                    jnp.asarray(np.stack([c[1] for c in chunks])),
                    stacked_sharding)
                span.set_metadata(bytes=w.nbytes + sum(
                    a.nbytes for a in arrs.values()))
            with TraceAnnotation("svi_dispatch"):
                scale = jnp.asarray(nc / B, jnp.float64)
                return prog(hyp, z, arrs["y"], arrs["mu"], arrs.get("s"), w,
                            fmask, n_full, scale)

        return step

    def streamed_predictive_state(self, hyp, z, stream, fmask=None,
                                  jitter: float = DEFAULT_JITTER,
                                  prefetch_depth: int = 2):
        """Training-to-serving handoff from a host stream: one streamed
        exact map-reduce -> the frozen ``serve.PredictiveState`` — the
        streaming analogue of :meth:`predictive_state`, bitwise the same
        state (the Stats it is extracted from are bitwise equal)."""
        from ..serve import extract_state

        st = self.streamed_stats(hyp, z, stream, fmask=fmask,
                                 prefetch_depth=prefetch_depth)
        return extract_state(hyp, z, st, jitter=jitter, kernel=self.kernel)

    # -- online updates (continual learning) --------------------------------
    def update_stats_fn(self, d: int):
        """Jitted distributed *fold*: absorb a new sharded block into
        already-reduced Stats.

        Signature: ``(base_stats, hyp, z, y_new, mu_new, s_new, w_new,
        fmask) -> Stats``.  Each shard computes the partial Stats of its
        slice of the new block locally (always the exact scan — fold /
        downdate identities need unscaled statistics), ONE psum reduces
        them (the same constant-size collective as training), and the
        replicated ``base_stats`` folds in element-wise
        (``stats.fold_stats``).  Cost is O(k_shard · m²) map + O(m² + md)
        reduce — independent of how much data the base Stats summarise,
        which is the whole point of online updates.

        To *forget* a sharded block, fold with ``base.scale(1.0)`` and
        subtract: ``downdate = stats.downdate_stats(base, delta)`` where
        ``delta`` comes from :meth:`reduced_stats` over the block — or
        simply negate the weights, since every statistic is w-linear.
        """

        def _fold(base, hyp, z, y, mu, s, w, fmask):
            idx = _flat_shard_index(self.mesh, self.data_axes)
            w = w * fmask[idx]
            st = self._local_stats(hyp, z, y, mu, s, w, exact=True)
            st = Stats(*(lax.psum(t, self.data_axes) for t in st))
            return fold_stats(base, st)

        f = shard_map(
            _fold,
            mesh=self.mesh,
            in_specs=(
                self._rep_spec,   # base_stats (replicated, constant-size)
                self._rep_spec, self._rep_spec, self._data_spec,
                self._data_spec, self._data_spec, self._data_spec,
                self._rep_spec,
            ),
            out_specs=self._rep_spec,
        )
        return jax.jit(f)

    def update_predictive_state(self, state, x_new, y_new, weights=None):
        """Serve-side incremental refresh on this engine's mesh: absorb a
        (replicated) block of k events into a served ``PredictiveState``
        in O(m²k) — rank-k factor update via ``serve.online``, no
        refactorisation, and NO collectives: the block is the same on
        every host (a serving tier ingests events, not training shards),
        so the refresh is replicated local math, the serving analogue of
        the zero-communication map (jaxpr-asserted in
        tests/_dist_worker.py).  Returns ``online.RefreshResult``.

        Training-side bookkeeping (the folded Stats for a later exact
        re-fit) is :meth:`update_stats_fn`'s job; this method only moves
        the serving factors."""
        from ..serve import online

        return online.update_state(state, x_new, y_new, weights)

    def downdate_predictive_state(self, state, x_old, y_old, weights=None):
        """Forget a (replicated) block from a served state: rank-k
        Cholesky downdate with the guarded refactorisation fallback —
        same collective-free contract as :meth:`update_predictive_state`."""
        from ..serve import online

        return online.downdate_state(state, x_old, y_old, weights)

    # -- serving ------------------------------------------------------------
    def predictive_state(self, hyp, z, y, mu, s, w, fmask=None,
                         jitter: float = DEFAULT_JITTER):
        """One exact map-reduce over the sharded data -> the frozen
        ``serve.PredictiveState`` (replicated; constant-size).  This is the
        training-to-serving handoff: after this call neither the engine nor
        the data shards are needed to answer queries — ``serve.save_state``
        the result and a server restarts from disk alone."""
        from ..serve import extract_state

        if self._stats_prog is None:
            self._stats_prog = self.reduced_stats(d=0)
        if fmask is None:
            fmask = jnp.ones((self.n_shards,))
        st = self._stats_prog(hyp, z, y, mu, s, w, fmask)
        return extract_state(hyp, z, st, jitter=jitter, kernel=self.kernel)

    def predict_engine(self, state, block_size: int = 256,
                       kernel_backend: str | None = None,
                       donate: bool = False):
        """A ``serve.PredictEngine`` sharding query batches over this
        engine's mesh/data axes (state replicated, predictions row-local —
        zero communication).  ``kernel_backend`` defaults to the training
        engine's backend."""
        from ..serve import PredictEngine

        return PredictEngine(
            state, block_size=block_size, mesh=self.mesh,
            data_axes=self.data_axes,
            kernel_backend=kernel_backend or self.kernel_backend,
            donate=donate)

    def multi_predict_engine(self, states, block_size: int = 256,
                             donate: bool = False, compute_dtype=None):
        """A ``serve.MultiPredictEngine`` serving N stacked states (an
        ensemble or A/B fleet) over this engine's mesh from one compiled
        executable: queries shard across the data axes, the stacked state
        is replicated, and — like ``predict_engine`` — predictions are
        row-local with zero collectives."""
        from ..serve import MultiPredictEngine

        return MultiPredictEngine(
            states, block_size=block_size, mesh=self.mesh,
            data_axes=self.data_axes, donate=donate,
            compute_dtype=compute_dtype)
