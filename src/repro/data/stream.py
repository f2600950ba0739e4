"""Host-streaming ingestion: block sources, shard-major chunking, prefetch.

The engines' in-memory path (``DistributedGP.put_data`` staging the whole
padded dataset, ``PredictEngine`` staging the whole padded query batch)
caps the reproduction at device/host RAM.  This module removes that cap
for both directions of the pipeline:

  * **Block sources** — a minimal random-access protocol (``n``, ``fields``,
    ``read(start, stop)``) over host data that never has to be resident at
    once: in-memory arrays (:class:`ArraySource`, the parity reference),
    memory-mapped ``.npy``/uncompressed ``.npz`` files
    (:class:`MemmapSource` — the npz members are mmapped in place through
    their zip offsets, no extraction), and deterministic chunk-addressable
    generators (:class:`SyntheticSource` — data that is *computed*, so host
    RSS is O(chunk) at any n, the >RAM benchmark regime).
  * **Shard-major chunking** (:class:`BlockStream`) — fixed-shape padded
    ``(block, weights)`` chunks laid out so that chunk ``c`` carries scan
    blocks ``[c·bpc, (c+1)·bpc)`` of EVERY shard's contiguous row range.
    Each shard therefore sees exactly the rows, in exactly the block
    partition and order, that ``pad_and_shard`` + the in-device
    ``lax.scan`` would give it — which is what makes streamed ingestion
    *bitwise* equal to the in-memory path (tests/test_stream_ingest.py),
    not merely close.
  * **Double-buffered prefetch** (:func:`prefetch`) — a bounded
    background-thread map that stages chunk ``i+1`` (host assembly +
    ``jax.device_put`` onto the mesh sharding) while the caller computes
    on chunk ``i``.  Jitted XLA programs release the GIL while executing,
    so host-side read/assembly genuinely overlaps device compute.

Training threads this through ``DistributedGP.put_data(stream=...)`` /
``streamed_stats`` / ``streamed_value_and_grad`` (host-fed outer loop over
``stats.partial_stats_chunked(init=...)``, shard memory O(block) in n) and
serving through ``PredictEngine.predict_stream`` / ``sample_stream``
(per-chunk results, the padded query set never materialises).  See
docs/training.md ("Streaming from disk") and docs/serving.md.
"""
from __future__ import annotations

import pathlib
import queue
import threading
import zipfile
from functools import partial
from typing import Callable, Iterable, Iterator

import numpy as np
from jax.profiler import annotate_function

__all__ = [
    "ArraySource", "MemmapSource", "SyntheticSource", "as_source",
    "BlockStream", "prefetch", "stage_to_device", "padded_rows",
    "open_npz_memmaps",
]


# -- block sources -----------------------------------------------------------
#
# A source is anything with:
#   n: int                              total real rows
#   fields: dict[str, tuple]            field name -> trailing shape
#   read(start, stop) -> dict[str, np.ndarray]   rows [start, stop), 0<=start
#                                       <=stop<=n, each (stop-start,)+trailing
#
# ``read`` must be cheap for any window (random access): the SVI chunk
# sampler and the two-pass streamed gradient both re-read arbitrary chunks.


class ArraySource:
    """In-memory dict-of-arrays source — the parity/testing reference, and
    what ``as_source`` wraps a plain dict into."""

    def __init__(self, arrs: dict):
        if not arrs:
            raise ValueError("ArraySource needs at least one field")
        self._arrs = {k: np.asarray(v) for k, v in arrs.items()}
        ns = {a.shape[0] for a in self._arrs.values()}
        if len(ns) != 1:
            raise ValueError(f"fields disagree on leading dim: {ns}")
        self.n = ns.pop()
        self.fields = {k: a.shape[1:] for k, a in self._arrs.items()}

    def read(self, start: int, stop: int) -> dict:
        return {k: a[start:stop] for k, a in self._arrs.items()}


def open_npz_memmaps(path) -> dict:
    """Memory-map every member of an *uncompressed* ``.npz`` in place.

    ``np.savez`` stores members ZIP_STORED (no deflate), so each embedded
    ``.npy`` is a contiguous byte range of the archive: seek past the zip
    local header, parse the npy header, and ``np.memmap`` the payload at
    its absolute offset.  Compressed members (``np.savez_compressed``)
    cannot be mapped — they fall back to a full in-memory load, which
    keeps small files working but forfeits the O(chunk) residency.
    """
    path = pathlib.Path(path)
    out = {}
    with zipfile.ZipFile(path) as zf:
        infos = {i.filename: i for i in zf.infolist()}
        for name, info in infos.items():
            key = name[:-4] if name.endswith(".npy") else name
            if info.compress_type != zipfile.ZIP_STORED:
                out[key] = np.load(path)[key]     # compressed: load fallback
                continue
            with open(path, "rb") as f:
                # Local file header: 30 fixed bytes + name + extra field
                # (the extra field can differ from the central directory's,
                # so it must be read from the local header itself).
                f.seek(info.header_offset + 26)
                name_len = int.from_bytes(f.read(2), "little")
                extra_len = int.from_bytes(f.read(2), "little")
                data_off = info.header_offset + 30 + name_len + extra_len
                f.seek(data_off)
                version = np.lib.format.read_magic(f)
                shape, fortran, dtype = np.lib.format._read_array_header(
                    f, version)
                payload_off = f.tell()
            out[key] = np.memmap(path, dtype=dtype, mode="r", shape=shape,
                                 offset=payload_off,
                                 order="F" if fortran else "C")
    return out


class MemmapSource:
    """Memory-mapped file-backed source: rows live in the page cache, not
    the process heap — reading a window touches O(window) bytes.

    Construct from per-field ``.npy`` paths (``MemmapSource({"y": "y.npy",
    "mu": "x.npy"})``) or a single ``.npz`` via :meth:`from_npz`.
    """

    def __init__(self, paths_or_arrays: dict):
        arrs = {}
        for k, v in paths_or_arrays.items():
            if isinstance(v, (str, pathlib.Path)):
                arrs[k] = np.load(v, mmap_mode="r")
            else:
                arrs[k] = v                     # already array-like / memmap
        self._src = ArraySource(arrs)
        self.n = self._src.n
        self.fields = self._src.fields

    @classmethod
    def from_npz(cls, path) -> "MemmapSource":
        return cls(open_npz_memmaps(path))

    def read(self, start: int, stop: int) -> dict:
        # np.asarray materialises just the window (memmap slices are lazy).
        return {k: np.asarray(v) for k, v in self._src.read(start, stop).items()}


class SyntheticSource:
    """Chunk-addressable generator source: rows are *computed* on demand by
    ``make_chunk(start, stop) -> dict``, deterministically per window, so a
    2M-row dataset occupies O(chunk) host memory (examples/flight_scale.py).

    ``make_chunk`` must be pure in (start, stop): the same window always
    yields the same rows (the SVI sampler and the streamed gradient's
    second pass re-read windows).  ``fields`` is probed with an empty-able
    1-row window unless given explicitly.
    """

    def __init__(self, n: int, make_chunk: Callable[[int, int], dict],
                 fields: dict | None = None):
        if n < 0:
            raise ValueError(f"n must be >= 0, got {n}")
        self.n = n
        self._make = make_chunk
        if fields is None:
            probe = make_chunk(0, min(1, n)) if n else {}
            fields = {k: np.asarray(v).shape[1:] for k, v in probe.items()}
        self.fields = dict(fields)

    def read(self, start: int, stop: int) -> dict:
        out = {k: np.asarray(v) for k, v in self._make(start, stop).items()}
        for k, v in out.items():
            if v.shape[0] != stop - start:
                raise ValueError(
                    f"make_chunk returned {v.shape[0]} rows for field {k!r}, "
                    f"expected {stop - start}")
        return out


def as_source(obj):
    """Coerce to a block source: dict of arrays -> ArraySource; an existing
    source (or BlockStream, unwrapped) passes through."""
    if isinstance(obj, BlockStream):
        return obj.source
    if isinstance(obj, dict):
        return ArraySource(obj)
    if hasattr(obj, "read") and hasattr(obj, "n") and hasattr(obj, "fields"):
        return obj
    raise TypeError(
        f"cannot stream from {type(obj).__name__}: expected a dict of "
        "arrays or an object with (n, fields, read)")


# -- shard-major fixed-shape chunking ---------------------------------------

def padded_rows(n: int, mult: int) -> int:
    """Padded leading dim: next multiple of ``mult`` >= max(n, 1) — the
    single source of truth shared with ``distributed.pad_and_shard``, so a
    stream's padded layout matches the staged one row-for-row.  n = 0 still
    yields one full multiple (a shape-static all-padding block) rather than
    empty arrays."""
    return max(n + (-n) % mult, mult)


class BlockStream:
    """Fixed-shape padded chunks of a source, in shard-major layout.

    The padded row space is the one ``pad_and_shard`` builds: ``n_pad =
    padded_rows(n, n_shards·block_size)`` rows, shard k owning the
    contiguous range ``[k·rps, (k+1)·rps)`` (``rps = n_pad / n_shards``),
    real rows first, zero-weight padding at the global tail.  Chunk ``c``
    then carries, for EVERY shard, its local scan blocks ``[c·bpc,
    (c+1)·bpc)`` — concatenated shard-by-shard into one
    ``(n_shards·bpc·block_size, ...)`` host array that ``jax.device_put``
    with the engine's data sharding splits back into per-shard block runs.

    Because each shard sees its in-memory rows in its in-memory block
    partition and order, folding the chunks through
    ``partial_stats_chunked(init=carry)`` reproduces the staged engine's
    scan *bitwise* — the layout is the parity contract, not an
    optimisation.  All assembly is host-side numpy over ``source.read``
    windows: O(chunk) resident regardless of n.

    Args:
      source: a block source (``as_source`` coercible).
      n_shards: mesh data-shard count (``DistributedGP.n_shards``).
      block_size: rows per device scan block (the engine's ``chunk_size``).
      blocks_per_chunk: scan blocks per shard per chunk — the H2D transfer
        granularity.  Larger chunks amortise dispatch; smaller chunks bound
        host memory and sharpen SVI sampling granularity.
    """

    def __init__(self, source, n_shards: int = 1, block_size: int = 1024,
                 blocks_per_chunk: int = 1):
        if n_shards < 1 or block_size < 1 or blocks_per_chunk < 1:
            raise ValueError(
                "n_shards, block_size and blocks_per_chunk must be >= 1, "
                f"got {n_shards}, {block_size}, {blocks_per_chunk}")
        self.source = as_source(source)
        self.n_shards = n_shards
        self.block_size = block_size
        self.n = self.source.n
        self.fields = dict(self.source.fields)
        self.n_pad = padded_rows(self.n, n_shards * block_size)
        self.rows_per_shard = self.n_pad // n_shards
        self.blocks_per_shard = self.rows_per_shard // block_size
        # Chunks never overshoot a shard's row range: an oversized
        # blocks_per_chunk clamps to the whole shard (one chunk), keeping
        # every chunk's per-shard block sequence a prefix-run of the
        # in-memory scan's (the bitwise-parity contract).
        blocks_per_chunk = min(blocks_per_chunk, self.blocks_per_shard)
        self.blocks_per_chunk = blocks_per_chunk
        self.n_chunks = -(-self.blocks_per_shard // blocks_per_chunk)
        # Rows per shard per chunk / total chunk rows (fixed for all chunks;
        # the tail chunk tops up with zero-weight blocks).
        self.shard_chunk_rows = blocks_per_chunk * block_size
        self.chunk_rows = n_shards * self.shard_chunk_rows

    def field_dtype(self, k):
        """Host dtype of field ``k`` (probed from a 0/1-row read)."""
        win = self.source.read(0, 0 if self.n == 0 else 1)
        return np.asarray(win[k]).dtype

    # A host span for a profiler trace: one per chunk assembled.
    @partial(annotate_function, name="chunk_assemble")
    def chunk(self, c: int):
        """Assemble chunk ``c`` -> ``(dict of (chunk_rows, ...) arrays,
        weights (chunk_rows,))``; weights are 1.0 exactly on real rows."""
        if not 0 <= c < max(self.n_chunks, 1):
            raise IndexError(f"chunk {c} out of range ({self.n_chunks})")
        out = {}
        w = np.zeros((self.chunk_rows,), np.float64)
        reads = []      # (dst_start, src_start, src_stop) real-row windows
        for k_sh in range(self.n_shards):
            lo = k_sh * self.rows_per_shard + c * self.shard_chunk_rows
            hi = min(lo + self.shard_chunk_rows,
                     (k_sh + 1) * self.rows_per_shard)
            real_hi = min(hi, self.n)           # padding = global tail rows
            if real_hi > lo:
                dst = k_sh * self.shard_chunk_rows
                reads.append((dst, lo, real_hi))
                w[dst:dst + (real_hi - lo)] = 1.0
        for k, trail in self.fields.items():
            # q(X) variances pad with 1s (log-safe), everything else 0s —
            # the pad_and_shard convention.
            cval = 1.0 if k in ("s", "S") else 0.0
            out[k] = np.full((self.chunk_rows,) + tuple(trail), cval,
                             dtype=self.field_dtype(k))
        for dst, lo, hi in reads:
            data = self.source.read(lo, hi)
            for k in self.fields:
                out[k][dst:dst + (hi - lo)] = data[k]
        return out, w

    def __len__(self) -> int:
        return self.n_chunks

    def __iter__(self) -> Iterator:
        return (self.chunk(c) for c in range(self.n_chunks))

    def chunks(self, indices: Iterable[int] | None = None) -> Iterator:
        """Iterate chunks — all of them, or an explicit index subset (the
        SVI sampler path)."""
        idx = range(self.n_chunks) if indices is None else indices
        return (self.chunk(int(c)) for c in idx)


# -- double-buffered prefetch ------------------------------------------------

class _PrefetchDone:
    pass


class _PrefetchError:
    def __init__(self, exc):
        self.exc = exc


def prefetch(it: Iterable, fn: Callable | None = None, depth: int = 2):
    """Map ``fn`` over ``it`` in a background thread, ``depth`` items ahead.

    The returned generator yields ``fn(item)`` in order.  With ``fn`` doing
    host assembly + ``jax.device_put`` (:func:`stage_to_device`), item
    ``i+1``'s read/pad/H2D overlaps the caller's device compute on item
    ``i`` — jitted programs release the GIL while XLA executes, so the
    overlap is real on a single host.  ``depth`` bounds how many staged
    items exist at once (2 = classic double buffering).  Worker exceptions
    re-raise at the consumer's next pull; abandoning the generator
    (``close`` / GC) unblocks and stops the worker.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def _worker():
        try:
            for item in it:
                staged = item if fn is None else fn(item)
                while not stop.is_set():
                    try:
                        q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            q.put(_PrefetchDone())
        except BaseException as e:  # noqa: BLE001 — relayed to the consumer
            try:
                q.put(_PrefetchError(e), timeout=1.0)
            except queue.Full:
                pass

    t = threading.Thread(target=_worker, daemon=True,
                         name="repro-stream-prefetch")
    t.start()

    def _gen():
        try:
            while True:
                item = q.get()
                if isinstance(item, _PrefetchDone):
                    return
                if isinstance(item, _PrefetchError):
                    raise item.exc
                yield item
        finally:
            stop.set()

    return _gen()


def stage_to_device(sharding=None):
    """A ``prefetch`` fn staging ``(arrays_dict, weights)`` chunks onto the
    device(s): ``jax.device_put`` each field (and the weight vector) with
    the given sharding (e.g. ``DistributedGP.data_sharding()``), or onto
    the default device when None."""
    import jax

    def _stage(chunk):
        arrs, w = chunk
        if sharding is None:
            return ({k: jax.device_put(v) for k, v in arrs.items()},
                    jax.device_put(w))
        return ({k: jax.device_put(v, sharding) for k, v in arrs.items()},
                jax.device_put(w, sharding))

    return _stage
