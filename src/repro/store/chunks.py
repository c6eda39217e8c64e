"""Content-defined chunking for large tensors (DESIGN.md §12).

Tensors at or above ``ArtifactStore.chunk_threshold`` are split into chunks
that become first-class CAS objects under the ``c_<sha256(bytes)>`` key
scheme. Boundaries come from a Gear-style rolling hash — a windowed hash of
the last ``WINDOW`` bytes, cut where ``hash & mask == 0`` — so a localized
edit only moves boundaries inside its own neighborhood and every other chunk
keeps its key (content-defined dedup, the XetHub/FastCDC idea). A fixed-grid
mode (``mode="fixed"``) exists as a deterministic fallback and as the shape
the RSS-budget CI smoke uses.

Two properties matter for the layers above:

* **Element alignment.** Every cut is snapped down to a multiple of the
  dtype itemsize, so each chunk decodes as a whole number of elements and
  per-chunk delta quantization (``store/delta.py``) never straddles a cut.
* **Segment confinement.** ``cut_points`` accepts hard segment boundaries
  (from ``dist/sharding.py`` shard splits); chunks never cross a segment,
  so each host of a sharded consumer can pull exactly its shard's chunks.

The pure-python byte loop of classic FastCDC is far too slow for GB-scale
tensors, so the rolling hash is vectorized: with window W=8 the Gear hash of
position ``i`` is ``G0[b[i]] ^ G1[b[i-1]] ^ ... ^ G7[b[i-7]]``. A cut test
reads only the hash's low bits (the mask is below 2^32), so the scan keeps
32 of its 64 bits and looks up two window bytes at a time in four
65536-entry pair tables — four gathers per byte, over cache-sized
sub-blocks. Each segment is scanned once; the greedy cut walk then only
searches the sorted candidate positions.
"""

from __future__ import annotations

import collections
import os
import threading
from concurrent import futures
from typing import Any, Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# Chunking defaults. Threshold chosen so ordinary layer tensors (a few MB)
# keep the PR-4 whole-tensor fold path; only genuinely large params pay the
# per-chunk manifest overhead.
DEFAULT_CHUNK_THRESHOLD = 8 * 2 ** 20    # params >= this are chunked
DEFAULT_MIN_CHUNK = 256 * 2 ** 10
DEFAULT_AVG_CHUNK = 1 * 2 ** 20          # must be a power of two (hash mask)
DEFAULT_MAX_CHUNK = 4 * 2 ** 20
DEFAULT_WINDOW_BYTES = 64 * 2 ** 20      # commit/checkout in-flight budget

WINDOW = 8                               # rolling-hash window, bytes
_SCAN_BLOCK = 1 * 2 ** 20                # sub-block for vectorized hashing

# 8 independent 256-entry u64 tables from a fixed-seed PRNG: boundary
# positions are a pure function of content, stable across processes/versions.
_GEAR = np.random.default_rng(0x4D476974).integers(
    0, 2 ** 64, size=(WINDOW, 256), dtype=np.uint64)


def _pair_tables() -> List[np.ndarray]:
    """Table k maps the byte pair ``b[i-2k] | b[i-2k-1] << 8`` to
    ``G(2k)[b[i-2k]] ^ G(2k+1)[b[i-2k-1]]`` (low 32 bits)."""
    g = (_GEAR & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pair = np.arange(1 << 16)
    return [g[2 * k][pair & 0xFF] ^ g[2 * k + 1][pair >> 8]
            for k in range(WINDOW // 2)]


_PAIRS = _pair_tables()


def _block_candidates(block: np.ndarray, mask: np.uint32) -> np.ndarray:
    """Offsets p >= WINDOW-1 in a u8 block whose Gear hash over bytes
    [p-7, p] hits the mask."""
    pair = block[1:].astype(np.uint16) | (block[:-1].astype(np.uint16) << 8)
    n = block.size - (WINDOW - 1)        # positions WINDOW-1 .. size-1
    top = WINDOW - 2                     # pair index of position WINDOW-1
    h = np.take(_PAIRS[0], pair[top:top + n])
    for k in range(1, WINDOW // 2):
        h ^= np.take(_PAIRS[k], pair[top - 2 * k:top - 2 * k + n])
    return np.flatnonzero((h & mask) == 0) + (WINDOW - 1)


def _candidates(read: Callable[[int, int], bytes], start: int, length: int,
                mask: int) -> np.ndarray:
    """Offsets p (relative to ``start``) in a ``length``-byte stream where
    the windowed hash over bytes [p-7, p] hits the mask, ascending.

    A cut at p means "chunk ends after byte p" (exclusive offset p+1). The
    stream is read in sub-blocks with a WINDOW-1 byte overlap so the
    temporaries stay bounded regardless of input size."""
    if mask >= 1 << 32:
        raise ValueError(f"chunk hash mask {mask:#x} exceeds 32 bits")
    mask32 = np.uint32(mask)
    out: List[np.ndarray] = [np.empty(0, dtype=np.int64)]
    off = 0
    while off < length - (WINDOW - 1):
        size = min(length - off, _SCAN_BLOCK + WINDOW - 1)
        block = np.frombuffer(read(start + off, size), dtype=np.uint8)
        out.append(_block_candidates(block, mask32).astype(np.int64) + off)
        off += _SCAN_BLOCK
    return np.concatenate(out)


def cut_points(read: Callable[[int, int], bytes], length: int, itemsize: int,
               *, min_size: int = DEFAULT_MIN_CHUNK,
               avg_size: int = DEFAULT_AVG_CHUNK,
               max_size: int = DEFAULT_MAX_CHUNK,
               mode: str = "cdc",
               segments: Optional[Sequence[int]] = None) -> List[int]:
    """Exclusive chunk-end offsets for a byte stream of ``length`` bytes.

    ``read(offset, size)`` supplies bytes on demand — the stream is scanned
    in bounded windows, never held whole. ``segments`` lists hard interior
    boundaries (ascending, itemsize-aligned); they are always cut points and
    chunking restarts at each, so no chunk crosses a shard boundary.
    Returns offsets ending with ``length``.
    """
    if itemsize <= 0:
        itemsize = 1
    min_size = max(itemsize, (min_size // itemsize) * itemsize or itemsize)
    max_size = max(min_size + itemsize, (max_size // itemsize) * itemsize)
    mask = max(1, int(avg_size)) - 1  # power-of-two avg → uniform hit rate

    bounds = [0]
    if segments:
        bounds.extend(int(s) for s in segments if 0 < int(s) < length)
    bounds.append(length)
    bounds = sorted(set(bounds))

    def snap(off: int) -> int:
        return (off // itemsize) * itemsize

    cuts: List[int] = []
    for seg_start, seg_end in zip(bounds[:-1], bounds[1:]):
        seg_len = seg_end - seg_start
        pos = 0
        cands = None
        while seg_len - pos > max_size:
            if mode == "fixed":
                # deterministic grid at the configured average size; the
                # tail chunk absorbs the remainder (up to max_size)
                cut = max(min_size, (avg_size // itemsize) * itemsize)
            else:
                # FastCDC-style greedy selection: the first candidate whose
                # window lies past pos and whose snapped offset lands in
                # [min_size, max_size], else a forced cut at max_size.
                # Offsets snap down to itemsize multiples so chunks hold
                # whole elements.
                if cands is None:
                    cands = _candidates(read, seg_start, seg_len, mask)
                first = pos + max(min_size - 1, WINDOW - 1)
                i = int(np.searchsorted(cands, first))
                if i < cands.size and cands[i] < pos + max_size:
                    cut = snap(int(cands[i]) - pos + 1)
                else:
                    cut = max(itemsize, snap(max_size))
            if seg_len - (pos + cut) < itemsize:
                break
            pos += cut
            cuts.append(seg_start + pos)
        cuts.append(seg_end)
    if not cuts or cuts[-1] != length:
        cuts.append(length)
    return sorted(set(c for c in cuts if 0 < c <= length))


def spans_of(cuts: Sequence[int]) -> List[Tuple[int, int]]:
    """(offset, length) pairs from exclusive cut offsets."""
    out = []
    prev = 0
    for c in cuts:
        out.append((prev, c - prev))
        prev = c
    return out


_END = object()   # ordered_stream: no task drawn yet / tasks exhausted


def ordered_stream(tasks: Iterable, work: Callable, consume: Callable,
                   cost: Callable[[Any], int], window: int,
                   executor=None) -> Tuple[int, int]:
    """Run ``work(task)`` for every task on ``executor`` and hand each
    result to ``consume(task, result)`` strictly in task order.

    Admission is by cost, not by batch: a task is submitted as soon as its
    ``cost`` plus that of every task admitted but not yet consumed stays
    within ``window``; a task costlier than the window runs alone, once
    nothing else is in flight. After each consumed result more tasks are
    admitted, so the workers keep running while the caller consumes. Tasks
    are drawn from ``tasks`` lazily, on the calling thread, one admission
    ahead of their submission.

    On any failure (a task, ``consume`` or the task iterator raising)
    admission stops, tasks not yet started are cancelled, running ones are
    waited for, and the first error in task order is re-raised here; the
    executor stays usable. Without an executor the tasks run inline.

    Returns ``(head_waits, window_stalls)``: how often the next result in
    order was still unfinished when the caller wanted it, and how often
    admission stopped because the next task would exceed the window."""
    if executor is None:
        for task in tasks:
            consume(task, work(task))
        return 0, 0
    it = iter(tasks)
    pending: "collections.deque" = collections.deque()  # (task, future, cost)
    failed = threading.Event()
    held = 0
    nxt: Any = _END
    head_waits = stalls = 0

    def note(fut) -> None:
        if not fut.cancelled() and fut.exception() is not None:
            failed.set()

    def admit() -> None:
        nonlocal held, nxt, stalls
        while not failed.is_set():
            if nxt is _END:
                nxt = next(it, _END)
                if nxt is _END:
                    return
            c = int(cost(nxt))
            if pending and held + c > window:
                stalls += 1
                return
            fut = executor.submit(work, nxt)
            fut.add_done_callback(note)
            pending.append((nxt, fut, c))
            held += c
            nxt = _END

    try:
        admit()
        while pending:
            task, fut, c = pending[0]
            if not fut.done():
                head_waits += 1
            result = fut.result()
            if failed.is_set():
                break
            consume(task, result)
            pending.popleft()
            held -= c
            admit()
        if failed.is_set():  # a later task failed: raise the first, in order
            for _, fut, _ in pending:   # the pool runs them FIFO: only
                fut.cancel()            # tasks after the failure are unstarted
            for _, fut, _ in pending:
                fut.result()
    except BaseException:
        for _, fut, _ in pending:
            fut.cancel()
        futures.wait([fut for _, fut, _ in pending])
        raise
    return head_waits, stalls


# ---------------------------------------------------------------------------
# Chunk sources: anything exposing shape/dtype plus random-access raw bytes.
# The commit engine never materializes more than its window of these.


class ArraySource:
    """Chunk-source view over an in-memory ndarray."""

    def __init__(self, arr: np.ndarray) -> None:
        self._arr = np.ascontiguousarray(arr)
        # a byte view: the buffer protocol has no format for ml_dtypes
        # (bfloat16, float8_*), so the array itself cannot be cast
        self._mv = memoryview(self._arr.reshape(-1).view(np.uint8))
        self.shape = tuple(int(d) for d in self._arr.shape)
        self.dtype = np.dtype(self._arr.dtype)
        self.nbytes = int(self._arr.nbytes)

    def read(self, offset: int, size: int) -> memoryview:
        return self._mv[offset:offset + size]


class FileSource:
    """Chunk source backed by raw little-endian bytes in a file (pread-based,
    no mmap — keeps page-cache pressure out of the process RSS budget)."""

    def __init__(self, path: str, shape: Sequence[int], dtype,
                 offset: int = 0) -> None:
        self.path = str(path)
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)
                          * self.dtype.itemsize) if self.shape else \
            self.dtype.itemsize
        self._base = int(offset)
        self._fd = os.open(self.path, os.O_RDONLY)

    def read(self, offset: int, size: int) -> bytes:
        parts = []
        pos = self._base + offset
        remaining = size
        while remaining > 0:
            b = os.pread(self._fd, remaining, pos)
            if not b:
                raise IOError(f"short read from {self.path} at {pos}")
            parts.append(b)
            pos += len(b)
            remaining -= len(b)
        return b"".join(parts) if len(parts) != 1 else parts[0]

    def close(self) -> None:
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass


class FnSource:
    """Procedural chunk source: ``fn(offset, size) -> bytes``. Lets the CI
    smoke commit a ~1 GB-logical tensor that never exists in memory."""

    def __init__(self, fn: Callable[[int, int], bytes],
                 shape: Sequence[int], dtype) -> None:
        self._fn = fn
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)
                          * self.dtype.itemsize) if self.shape else \
            self.dtype.itemsize

    def read(self, offset: int, size: int) -> bytes:
        return self._fn(offset, size)


def as_source(value):
    """Normalize a param value into a chunk source, or None if it already
    is one (has shape/dtype/read)."""
    if hasattr(value, "read") and hasattr(value, "shape") \
            and hasattr(value, "dtype"):
        return value
    return ArraySource(np.asarray(value))


def is_chunk_source(value) -> bool:
    return hasattr(value, "read") and hasattr(value, "shape") \
        and hasattr(value, "dtype") and not isinstance(value, np.ndarray)
