"""Partial sufficient statistics — the paper's Map step.

Each worker holds a shard ``(Y_k, mu_k, S_k)`` (regression: ``S_k = 0``,
``mu_k = X_k``) and computes

    A_k  = Sum_i Y_i Y_i^T            (scalar)
    B_k  = Sum_i psi0_i               (scalar)
    C_k  = Psi1_k^T Y_k               (m, d)
    D_k  = Sum_i psi2_i               (m, m)
    KL_k = Sum_i KL(q(X_i) || p(X_i)) (scalar)

These are exactly the terms the paper's end-point nodes return to the
central node (its §3.2 step 2); their size is independent of n.

``weights`` lets callers mask out padded rows (distributed padding) and
failed nodes (the paper's §5.2 drop-partial-term strategy) without changing
shapes — a zero weight removes point i from every statistic.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import covariance as cov

Array = jax.Array


def reg_stats_dense(hyp: dict, z: Array, x: Array, y: Array, w: Array,
                    kernel: "cov.Kernel | None" = None):
    """Monolithic XLA regression statistics ``(b, C, D)`` — the canonical
    map math shared by :func:`partial_stats` (``s is None`` branch) and the
    fused Pallas op's custom_vjp backward (``kernels.reg_stats``).
    Materialises the (n, m) kernel slab; the fused kernel is the version
    that does not.  ``kernel`` picks the covariance expression (None =
    SE-ARD, the pre-compositional default)."""
    kernel = cov.as_kernel(kernel)
    knm = kernel.K(hyp, x, z)                                  # (n, m)
    b = jnp.sum(w * kernel.kdiag(hyp, x))
    c = knm.T @ (w[:, None] * y)                               # (m, d)
    d_stat = (knm * w[:, None]).T @ knm                        # (m, m)
    return b, c, d_stat


class Stats(NamedTuple):
    """Sufficient statistics of the collapsed bound. All sums over points."""

    A: Array   # () Frobenius term  Sum Y_i Y_i^T
    B: Array   # () psi0 sum
    C: Array   # (m, d) Psi1^T Y
    D: Array   # (m, m) Psi2
    KL: Array  # () KL(q(X)||p(X))
    n: Array   # () effective number of points contributing

    def __add__(self, other: "Stats") -> "Stats":  # type: ignore[override]
        return Stats(*(a + b for a, b in zip(self, other)))

    def __sub__(self, other: "Stats") -> "Stats":
        return Stats(*(a - b for a, b in zip(self, other)))

    def scale(self, c) -> "Stats":
        return Stats(*(c * t for t in self))


def partial_stats(
    hyp: dict,
    z: Array,
    y: Array,
    mu: Array,
    s: Array | None = None,
    weights: Array | None = None,
    latent: bool = True,
    psi2_fn=None,
    reg_stats_fn=None,
    kernel: "cov.Kernel | None" = None,
) -> Stats:
    """Compute the shard-local statistics (the map function).

    Args:
      hyp: kernel/noise hyper-parameters (log-space dict).
      z: (m, q) inducing inputs.
      y: (n_k, d) outputs on this shard.
      mu: (n_k, q) q(X) means (== inputs X for regression).
      s: (n_k, q) q(X) variances, or None for regression (treated as 0).
      weights: (n_k,) 0/1 mask (padding / failed points). None = all ones.
      latent: include the KL term (GPLVM) or not (regression).
      psi2_fn: override for the psi2 accumulation (e.g. the Pallas kernel).
      reg_stats_fn: override for the regression (B, C, D) accumulation —
        ``fn(hyp, z, mu, y, w) -> (b, c, d)`` (e.g. the fused Pallas kernel,
        which never materialises the (n, m) slab in HBM).
      kernel: covariance expression (``core.covariance``); None = SE-ARD.
        Overrides *only* the default accumulations — an explicit
        ``psi2_fn`` / ``reg_stats_fn`` hook is expected to already be
        bound to the right kernel (the ops-layer shims do this).
    """
    kernel = cov.as_kernel(kernel)
    n_k = y.shape[0]
    w = jnp.ones((n_k,), y.dtype) if weights is None else weights.astype(y.dtype)

    if s is None:
        # Regression: q(X_i) is a delta at the observed inputs. Use the exact
        # kernel forms (cheaper + numerically exact) rather than S->0 limits.
        a = jnp.sum(w * jnp.sum(y * y, axis=-1))
        if reg_stats_fn is None:
            b, c, d_stat = reg_stats_dense(hyp, z, mu, y, w, kernel=kernel)
        else:
            b, c, d_stat = reg_stats_fn(hyp, z, mu, y, w)
        kl = jnp.zeros((), y.dtype)
    else:
        # Named scopes split the latent map (and its backward) in a trace.
        a = jnp.sum(w * jnp.sum(y * y, axis=-1))
        with jax.named_scope("psi1_c"):
            b = jnp.sum(w * kernel.psi0(hyp, mu, s))
            p1 = kernel.psi1(hyp, z, mu, s)                    # (n, m)
            c = p1.T @ (w[:, None] * y)
        with jax.named_scope("psi2"):
            if psi2_fn is None:
                d_stat = kernel.psi2(hyp, z, mu, s, w)
            else:
                d_stat = psi2_fn(hyp, z, mu, s, w)
        kl_i = 0.5 * jnp.sum(s + mu * mu - jnp.log(s) - 1.0, axis=-1)
        kl = jnp.sum(w * kl_i) if latent else jnp.zeros((), y.dtype)

    return Stats(A=a, B=b, C=c, D=d_stat, KL=kl, n=jnp.sum(w))


def zero_stats(m: int, d: int, dtype=jnp.float64) -> Stats:
    """The additive identity of the Stats monoid — a reduce/fold init for
    host-side accumulation. (The scan in ``partial_stats_chunked`` builds
    its own carry with scalars promoted to rank 1; see the note there.)"""
    zf = jnp.zeros((), dtype)
    return Stats(A=zf, B=zf, C=jnp.zeros((m, d), dtype),
                 D=jnp.zeros((m, m), dtype), KL=zf, n=zf)


def sample_block_indices(key: Array, n_blocks: int, batch_blocks: int) -> Array:
    """Uniform size-``batch_blocks`` subset of ``range(n_blocks)``, without
    replacement — the SVI block sampler.

    Sampling without replacement keeps the subset-mean identity exact:
    E[sum over sampled blocks] = (batch_blocks / n_blocks) * (sum over all
    blocks), which is what makes the ``n_blocks / batch_blocks`` reweighting
    in :func:`partial_stats_chunked` an unbiased estimator of the exact
    streamed statistics.  Returns ``(batch_blocks,)`` integer indices.
    """
    return jax.random.permutation(key, n_blocks)[:batch_blocks]


# The map's ops, and their backward, carry the ``map`` named scope in their
# HLO op_name metadata, which a profiler trace reads (no op changes).
@jax.named_scope("map")
def partial_stats_chunked(
    hyp: dict,
    z: Array,
    y: Array,
    mu: Array,
    s: Array | None = None,
    weights: Array | None = None,
    latent: bool = True,
    psi2_fn=None,
    reg_stats_fn=None,
    block_size: int | None = 1024,
    batch_blocks: int | None = None,
    key: Array | None = None,
    block_indices: Array | None = None,
    kernel: "cov.Kernel | None" = None,
    init: Stats | None = None,
    force_scan: bool = False,
    block_reduce_fn=None,
    reduce_buffered: bool = True,
) -> Stats:
    """Streaming map step: ``partial_stats`` folded over fixed-size row blocks.

    Exact mode (default) scans *every* block; minibatch (SVI) mode scans a
    random size-``batch_blocks`` subset and reweights, making the per-call
    cost O(batch_blocks * block_size) — independent of ``n_k``.

    Args:
      hyp: kernel/noise hyper-parameters (log-space dict).
      z: (m, q) inducing inputs.
      y: (n_k, d) outputs on this shard.
      mu: (n_k, q) q(X) means (== the inputs X for regression).
      s: (n_k, q) q(X) variances, or None for regression.
      weights: (n_k,) 0/1 row mask (padding / failed points). None = ones.
      latent: include the per-point KL term (GPLVM) or not (regression).
      psi2_fn / reg_stats_fn: per-block accumulation hooks (e.g. the Pallas
        kernels); invoked once per scanned block on block-sized operands.
      block_size: rows per scan block (default 1024). ``None`` delegates to
        the monolithic :func:`partial_stats` — so callers can dispatch on a
        single optional chunk-size setting.
      batch_blocks: if set, enables the stochastic (SVI) map: only
        ``batch_blocks`` of the ``nb = ceil(n_k / block_size)`` blocks are
        visited, chosen uniformly without replacement, and the accumulated
        Stats are scaled by ``nb / batch_blocks``.  Because every field of
        ``Stats`` is a plain sum over points (including the per-point KL and
        the effective count ``n``), the scaled Stats — and any function that
        is linear in them — are *unbiased* estimates of the exact streamed
        values; see docs/training.md for the derivation and for which bound
        terms inherit exact unbiasedness.  ``batch_blocks >= nb`` degrades
        gracefully to the exact scan (scale 1).  Requires ``block_size``.
      key: PRNG key for the block sampler (required in SVI mode unless
        ``block_indices`` is given). Pass a fresh key per optimiser step.
      block_indices: explicit (batch_blocks,) block indices, overriding the
        sampler — deterministic replay / subset-enumeration tests / custom
        block samplers plug in here.
      init: starting carry (rank-proper Stats, e.g. a previous call's
        return) folded exactly as if this call's blocks continued that
        scan: the body keeps adding ``carry + block`` left-to-right, so a
        host-fed outer loop threading ``init`` across fixed-shape chunks
        (``data.stream``) reproduces the single in-device scan *bitwise* —
        same float-add association, same per-block program.  Leaf dtypes
        must match the block output dtypes.  Incompatible with
        ``batch_blocks`` (the SVI reweighting scales the whole
        accumulated carry, which would corrupt a prior-chunk ``init``).
      force_scan: take the ``lax.scan`` path even when the rows fit one
        block (``n_k <= block_size``), instead of the monolithic
        short-circuit.  The distributed engine sets this so the bound's
        producer is a scan boundary regardless of shard size — XLA then
        compiles the global (post-psum) math identically whether the
        stats come from an in-device map or a streamed carry, which the
        streamed/in-memory bitwise-bound contract relies on.  No-op when
        ``block_size`` is None.
      block_reduce_fn: the *overlapped reduce* hook (``Stats -> Stats``,
        e.g. a per-leaf ``lax.psum`` bound to the mesh data axes).  When
        set, the scan no longer accumulates shard-local statistics for a
        single post-scan collective: each block's constant-size Stats
        contribution is reduced across shards *inside* the scan and the
        carry accumulates already-reduced values, so the collective for
        block t rides behind block t+1's compute instead of serialising
        after the whole map.  The returned Stats are then already
        globally reduced — callers must NOT psum them again.  Requires
        ``block_size`` (there is nothing to overlap without blocks) and
        is incompatible with ``init`` (a prior-chunk carry is shard-local
        by construction).  Composes with ``batch_blocks``: the sampled
        blocks are reduced as they are scanned and the uniform
        ``nb / batch_blocks`` reweighting is applied to the reduced
        accumulator (every shard's padded geometry gives the same scale,
        so scaling before or after the cross-shard sum commutes exactly
        in real arithmetic and the estimator stays unbiased).
      reduce_buffered: scheduling of the overlapped reduce (only
        meaningful with ``block_reduce_fn``).  True (default) double-
        buffers: the carry holds block t's raw Stats as a ``pending``
        slot and folds ``block_reduce_fn(pending)`` — block t-1's
        reduction — at step t, leaving the collective with no data
        dependence on step t's block compute (XLA's scheduler can
        overlap them); one flush reduces the final pending block after
        the scan.  False reduces each block eagerly in its own step.
        Both orders fold the same reduced values left-to-right, so they
        are BITWISE equal — double-buffering is a pure scheduling
        transformation (asserted in tests/_dist_worker.py).

    Exact mode is mathematically identical to :func:`partial_stats` (every
    statistic is a plain sum over points), but ``lax.scan``s over
    ``ceil(n_k / block_size)`` blocks of ``block_size`` rows, folding each
    block's Stats into a constant-size carry.  Peak live memory is therefore
    O(block_size * (m + q + d)) + O(m^2) — *independent of n_k* — instead of
    the monolithic path's O(n_k m^2) (the GPLVM psi2 broadcast) or
    O(n_k m) (regression).  This is what lets a shard stream more rows than
    fit in its device buffer (paper §5: the 2M-record flight experiment).

    Rows are padded up to a multiple of ``block_size`` with zero weight, so
    every scan step has identical shapes and padding contributes nothing —
    in SVI mode a sampled padding-heavy final block is handled by the same
    mechanism (its rows carry zero weight; the reweighting stays unbiased
    because the scale is uniform across blocks).
    """
    n_k = y.shape[0]
    if batch_blocks is not None:
        if block_size is None:
            raise ValueError(
                "batch_blocks (SVI mode) requires block_size: the minibatch "
                "is a subset of the streaming row blocks")
        if batch_blocks < 1:
            raise ValueError(f"batch_blocks must be >= 1, got {batch_blocks}")
        if init is not None:
            raise ValueError(
                "init cannot be combined with batch_blocks: the SVI "
                "reweighting scales the whole carry, prior chunks included")
    if block_reduce_fn is not None:
        if block_size is None:
            raise ValueError(
                "block_reduce_fn (overlapped reduce) requires block_size: "
                "the per-block collective needs blocks to hide behind")
        if init is not None:
            raise ValueError(
                "init cannot be combined with block_reduce_fn: a prior-"
                "chunk carry is shard-local, the overlapped carry is "
                "already reduced")
        force_scan = True
    if block_size is None or (n_k <= block_size and not force_scan):
        # Single block (or streaming disabled) — no scan machinery needed.
        # With batch_blocks set this is the nb == 1 degenerate case: the
        # "subset" is the whole data, i.e. the exact statistics.
        st = partial_stats(hyp, z, y, mu, s, weights=weights,
                           latent=latent, psi2_fn=psi2_fn,
                           reg_stats_fn=reg_stats_fn, kernel=kernel)
        return st if init is None else fold_stats(init, st)

    w = jnp.ones((n_k,), y.dtype) if weights is None else weights.astype(y.dtype)
    pad = (-n_k) % block_size
    nb = (n_k + pad) // block_size

    def blocks(a, cval=0.0):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=cval).reshape(
            (nb, block_size) + a.shape[1:])

    y_b, mu_b, w_b = blocks(y), blocks(mu), blocks(w)
    # q(X) variances padded with 1s: log-safe, and masked out by w=0 anyway.
    s_b = None if s is None else blocks(s, cval=1.0)

    xs = (y_b, mu_b, w_b) if s is None else (y_b, mu_b, s_b, w_b)

    # -- SVI: gather the sampled blocks, scan only those, reweight ----------
    # Explicit block_indices are always honored (deterministic replay or a
    # custom sampler, possibly with replacement), even at batch_blocks >= nb
    # where the key-driven sampler would degrade to the exact scan.
    scale = 1.0
    if batch_blocks is not None and (batch_blocks < nb
                                     or block_indices is not None):
        if block_indices is None:
            if key is None:
                raise ValueError(
                    "SVI mode needs a PRNG key (or explicit block_indices)")
            block_indices = sample_block_indices(key, nb, batch_blocks)
        idx = jnp.asarray(block_indices)
        if idx.shape != (batch_blocks,):
            raise ValueError(
                f"block_indices must have shape ({batch_blocks},), "
                f"got {idx.shape}")
        xs = tuple(jnp.take(a, idx, axis=0) for a in xs)
        scale = nb / batch_blocks

    def block_stats(yc, muc, sc, wc):
        return partial_stats(hyp, z, yc, muc, sc, weights=wc,
                             latent=latent, psi2_fn=psi2_fn,
                             reg_stats_fn=reg_stats_fn, kernel=kernel)

    # The carry keeps every leaf at rank >= 1 (scalars as (1,)): rank-0 scan
    # residuals trip shard_map's residual promotion on some JAX versions
    # when the chunked map runs (and is differentiated) inside the
    # distributed engine.
    def _block_of(xs_t):
        if s is None:
            yc, muc, wc = xs_t
            return block_stats(yc, muc, None, wc)
        yc, muc, sc, wc = xs_t
        return block_stats(yc, muc, sc, wc)

    def body(carry, xs_t):
        st = _block_of(xs_t)
        return Stats(*(c + jnp.atleast_1d(t) for c, t in zip(carry, st))), None

    # Carry init matches one block's output dtypes exactly (abstract eval —
    # works for any psi2_fn backend, including the Pallas kernel). A caller
    # init (host-fed chunk loop) slots in with the same rank-1 promotion,
    # so continuing a scan here adds the same bits the one-shot scan would.
    shapes = jax.eval_shape(
        block_stats, y_b[0], mu_b[0], None if s is None else s_b[0], w_b[0])

    if block_reduce_fn is not None:
        zero = Stats(*(jnp.zeros(t.shape or (1,), t.dtype) for t in shapes))

        def _fold_reduced(acc, raw):
            red = block_reduce_fn(raw)
            return Stats(*(a + jnp.atleast_1d(t) for a, t in zip(acc, red)))

        if reduce_buffered:
            # Double buffer: step t folds the reduction of block t-1's
            # pending Stats (no data dependence on block t's compute) and
            # parks block t's raw Stats as the new pending; a post-scan
            # flush reduces the last block.  The fold order over real
            # blocks is identical to the eager path's — the initial
            # pending is exact zeros and x + 0.0 == x bitwise — so the
            # two schedules produce bit-identical Stats.
            def body_ov(carry, xs_t):
                acc, pending = carry
                st = _block_of(xs_t)
                acc = _fold_reduced(acc, pending)
                pending = Stats(*(jnp.atleast_1d(t) for t in st))
                return (acc, pending), None

            (acc, pending), _ = jax.lax.scan(body_ov, (zero, zero), xs)
            acc = _fold_reduced(acc, pending)
        else:
            def body_ev(acc, xs_t):
                st = _block_of(xs_t)
                st = Stats(*(jnp.atleast_1d(t) for t in st))
                return _fold_reduced(acc, st), None

            acc, _ = jax.lax.scan(body_ev, zero, xs)
        out = Stats(*(t.reshape(sh.shape) for t, sh in zip(acc, shapes)))
        return out.scale(scale) if scale != 1.0 else out

    if init is None:
        carry0 = Stats(*(jnp.zeros(t.shape or (1,), t.dtype) for t in shapes))
    else:
        carry0 = Stats(*(jnp.atleast_1d(t) for t in init))
    out, _ = jax.lax.scan(body, carry0, xs)
    out = Stats(*(t.reshape(sh.shape) for t, sh in zip(out, shapes)))
    # Every Stats field is a per-point sum, so one uniform scale makes the
    # whole tuple (A, B, C, D, KL, n) unbiased for the exact scan. The
    # bound's global regulariser structure (log-det / quadratic in Kmm) is
    # a *function of* these stats, not itself a per-point sum — it is never
    # scaled here (docs/training.md, "which terms scale").
    return out.scale(scale) if scale != 1.0 else out


def reduce_stats(parts: list[Stats]) -> Stats:
    """Sequential reduce (the single-host analogue of the paper's reduce)."""
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


# -- online posterior updates (continual learning) --------------------------
#
# Every field of ``Stats`` is a plain sum over points, so the statistics of
# a union of data blocks are the element-wise sum of the blocks' statistics.
# That additivity is what the paper's map-reduce exploits *spatially*
# (across shards); ``fold_stats``/``downdate_stats`` exploit it *temporally*:
# a trained model absorbs a new block (or forgets an old one) by adding
# (subtracting) the block's partial Stats into its reduced Stats — no
# re-scan of history, cost independent of how much data came before.

def fold_stats(base: Stats, delta: Stats) -> Stats:
    """Fold a block's partial Stats into reduced Stats: ``stats(A ∪ B)``
    from ``stats(A)`` and ``stats(B)`` — exact, O(m² + md).

    Both arguments must be *exact* (unscaled) statistics for the identity
    to be exact.  SVI-reweighted Stats (``partial_stats_chunked`` with
    ``batch_blocks``) are unbiased *estimates* of the exact ones: folding
    one in yields an unbiased estimate of the folded Stats (the reweighting
    is linear, so it commutes with the fold), but ``downdate_stats`` then
    only undoes it in expectation — the online engines (``SGPR.update``,
    ``DistributedGP.update_stats_fn``) therefore always compute block
    deltas with the exact scan.  Zero-weight rows (distributed padding,
    failed points) already contribute nothing to ``delta`` and need no
    special handling here.
    """
    return base + delta


def downdate_stats(base: Stats, delta: Stats) -> Stats:
    """Remove a block's partial Stats: the exact inverse of
    :func:`fold_stats` (``downdate_stats(fold_stats(s, d), d) == s`` up to
    float addition error).

    ``delta`` must be the statistics of a block previously folded in,
    computed at the *same* hyper-parameters and inducing inputs — Stats are
    a function of (hyp, z), so a fit between fold and downdate invalidates
    the cached block deltas (recompute them from the stored rows, as
    ``SGPR.forget`` does).  Downdating a block that was never folded can
    leave ``D`` indefinite; downstream factor refreshes guard against that
    (``serve.online``).
    """
    return base - delta
