"""Public wrappers for the storage-path kernels.

Backends: ``"pallas"`` runs the compiled Pallas kernels (the default on a
TPU), ``"interpret"`` runs the same kernels in the Pallas interpreter (tests
on CPU), and ``"ref"`` runs the jnp oracles of ``ref.py`` (the default
elsewhere — same semantics, no interpreter overhead).

Canonicalization: every tensor is flattened and zero-padded to a
(rows, LANE_COLS) layout whose rows are a whole number of row blocks, each a
multiple of 32 rows (the int8 tile), dispatched to the kernel, and cropped
back to the original shape; zero counts are corrected for padding and the
fingerprint masks it, so callers never see the canonical layout. Per kernel,
one jitted device program per (shape, dtype) fuses the pad, the kernel, the
crop and the reduction of the per-tile partials; the wrapper then reads its
scalars back in one transfer.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import ref as _ref
from repro.kernels.chain_apply import chain_apply_2d, chain_apply_ref
from repro.kernels.delta_quantize import (BLOCK_ROWS, LANE_COLS,
                                          MIN_BLOCK_ROWS, PARTIAL,
                                          delta_quantize_2d, dequant_apply_2d)
from repro.kernels.fingerprint import fingerprint_2d
from repro.kernels.snapshot_fused import snapshot_fused_2d, snapshot_fused_ref
from repro.obs import REGISTRY

#: Pallas dispatches per kernel (compiled or interpreted; the jnp oracles
#: are not counted), scrapeable as mgit_kernel_dispatches_*.
DISPATCHES = REGISTRY.group(
    "mgit_kernel_dispatches",
    keys=("delta_quantize", "dequant_apply", "chain_apply", "snapshot_fused",
          "fingerprint"),
    help="Pallas storage-kernel dispatches, by kernel")


def default_backend() -> str:
    """The backend a ``backend=None`` caller gets: the compiled kernels on
    a TPU, the jnp oracles anywhere else. Asking starts JAX's backend, so
    host-only callers resolve it only when a kernel is actually needed."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _layout(n: int) -> Tuple[int, int]:
    """(rows, block_rows) of the canonical layout holding ``n`` elements."""
    rows = -(-max(n, 1) // LANE_COLS)
    rows = -(-rows // MIN_BLOCK_ROWS) * MIN_BLOCK_ROWS
    block = min(BLOCK_ROWS, rows)
    return -(-rows // block) * block, block


def _canon(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    flat = jnp.ravel(x)
    flat = jnp.pad(flat, (0, rows * LANE_COLS - flat.shape[0]))
    return flat.reshape(rows, LANE_COLS)


def _crop(x2d: jnp.ndarray, shape) -> jnp.ndarray:
    return x2d.reshape(-1)[:math.prod(shape)].reshape(shape)


#: The runtime zero the rounding kernels take (see ``delta_quantize``): a
#: jit argument, so that no compiler, XLA's included, can fold it away.
_ZERO = np.zeros((1,), np.int32)


def _count(kernel: str, backend: str) -> bool:
    """Count one kernel dispatch; True when it runs in the interpreter."""
    DISPATCHES[kernel] += 1
    return backend == "interpret"


# ---------------------------------------------------------------------------
# device programs: one jit per (shape, dtype, eps, backend)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _delta_quantize_dev(zero, p1, p2, *, eps, interpret):
    rows, block = _layout(p1.size)
    q, zeros = delta_quantize_2d(
        zero, _canon(p1.astype(jnp.float32), rows),
        _canon(p2.astype(jnp.float32), rows),
        eps=eps, block_rows=block, interpret=interpret)
    n_pad = rows * LANE_COLS - p1.size  # padded zeros quantize to 0
    per_block = jnp.sum(zeros.reshape(-1, PARTIAL[0] * PARTIAL[1]), axis=1)
    return _crop(q, p1.shape), jnp.sum(zeros) - n_pad, per_block


@functools.partial(jax.jit, static_argnames=("eps", "out_dtype", "interpret"))
def _dequant_apply_dev(zero, p1, q, *, eps, out_dtype, interpret):
    rows, block = _layout(p1.size)
    if q.dtype != jnp.int8:
        q = q.astype(jnp.int32)
    out = dequant_apply_2d(zero, _canon(p1.astype(jnp.float32), rows),
                           _canon(q, rows), eps=eps, block_rows=block,
                           interpret=interpret)
    return _crop(out, p1.shape).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "out_dtype", "interpret"))
def _chain_apply_dev(zero, base, qs, *, eps, out_dtype, interpret):
    rows, block = _layout(base.size)
    # narrowed int8 hops stream at one byte each; any wide hop widens all
    qdt = (jnp.int8 if all(q.dtype == jnp.int8 for q in qs) else jnp.int32)
    stack = jnp.stack([_canon(q.astype(qdt), rows) for q in qs])
    out = chain_apply_2d(zero, _canon(base.astype(jnp.float32), rows), stack,
                         eps=eps, block_rows=block, interpret=interpret)
    return _crop(out, base.shape).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _snapshot_dev(zero, p1, p2, *, eps, interpret):
    rows, block = _layout(p1.size)
    q8, zeros, ovf, h1, h2 = snapshot_fused_2d(
        zero, _canon(p1.astype(jnp.float32), rows),
        _canon(p2.astype(jnp.float32), rows), n=p1.size,
        eps=eps, block_rows=block, interpret=interpret)
    n_pad = rows * LANE_COLS - p1.size
    fp = jnp.stack([jnp.sum(h1, dtype=jnp.uint32),
                    jnp.sum(h2, dtype=jnp.uint32)])
    return _crop(q8, p1.shape), jnp.sum(zeros) - n_pad, jnp.sum(ovf), fp


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fingerprint_dev(x, *, interpret):
    rows, block = _layout(x.size)
    return fingerprint_2d(_canon(_ref.fingerprint_bits(x), rows), n=x.size,
                          block_rows=block, interpret=interpret)


_fingerprint_ref = jax.jit(_ref.fingerprint_ref)


# ---------------------------------------------------------------------------
# delta quantize / dequantize
# ---------------------------------------------------------------------------

def delta_quantize(p1, p2, eps: float = 1e-4, backend: Optional[str] = None,
                   return_block_zeros: bool = False):
    """Quantized delta q = floor((p1-p2)/scale + 0.5) (paper Algorithm 1).

    Returns (q int32 array shaped like p1, n_zero int) — optionally also the
    per-row-block zero counts used by the compressibility pre-filter.
    """
    backend = backend or default_backend()
    p1 = jnp.asarray(p1)
    p2 = jnp.asarray(p2)
    if backend == "ref":
        q, nz = _ref.delta_quantize_ref(p1, p2, eps)
        if return_block_zeros:
            return q, int(nz), None
        return q, int(nz)
    q, nz, per_block = _delta_quantize_dev(
        _ZERO, p1, p2, eps=eps, interpret=_count("delta_quantize", backend))
    if return_block_zeros:
        return q, int(nz), np.asarray(per_block)
    return q, int(nz)


def dequant_apply(p1, q, eps: float = 1e-4, out_dtype=None,
                  backend: Optional[str] = None):
    """Reconstruct the child parameter: p2' = p1 - q*scale."""
    backend = backend or default_backend()
    p1 = jnp.asarray(p1)
    q = jnp.asarray(q)
    if backend == "ref":
        return _ref.dequant_apply_ref(p1, q.astype(jnp.int32), eps,
                                      out_dtype=out_dtype)
    return _dequant_apply_dev(
        _ZERO, p1, q, eps=eps, out_dtype=np.dtype(out_dtype or p1.dtype),
        interpret=_count("dequant_apply", backend))


def chain_apply(base, qs: Sequence, eps: float = 1e-4, out_dtype=None,
                backend: Optional[str] = None):
    """Fused delta-chain application: ``base - sum(qs) * scale`` (§10.2).

    ``qs`` is a sequence of quantized deltas (int8/int32) from one same-eps
    chain segment. One HBM pass on TPU (int32 reduction in VMEM); bit-
    identical to summing on the host and calling ``dequant_apply`` once —
    int32 sums are exact, and the final multiply+subtract is the same
    correctly-rounded f32 op either way."""
    backend = backend or default_backend()
    base = jnp.asarray(base)
    qs = tuple(jnp.asarray(q).reshape(base.shape) for q in qs)
    out_dtype = np.dtype(out_dtype or base.dtype)
    if backend == "ref":
        out = chain_apply_ref(base, jnp.stack(qs).astype(jnp.int32), eps)
        return out.astype(out_dtype)
    return _chain_apply_dev(_ZERO, base, qs, eps=eps, out_dtype=out_dtype,
                            interpret=_count("chain_apply", backend))


# ---------------------------------------------------------------------------
# fused snapshot + fingerprint
# ---------------------------------------------------------------------------

def snapshot_fused(p1, p2, eps: float = 1e-4, backend: Optional[str] = None,
                   with_fingerprint: bool = True):
    """One-pass checkpoint snapshot: (q int8|int32, n_zero, fingerprint, narrow).

    Fuses delta_quantize + fingerprint(p2) into a single HBM pass (9 bytes
    per fp32 param vs 16 unfused; §Perf-C) and narrows q to int8 when every
    value fits; tensors with overflow fall back to int32 (`narrow=False`).
    ``with_fingerprint=False`` elides the fingerprint (returned as None) —
    the commit pipeline keys objects by SHA-256 and never reads it, and on
    the ref backend the fingerprint is a separate full pass worth skipping.
    """
    backend = backend or default_backend()
    p1 = jnp.asarray(p1)
    p2 = jnp.asarray(p2)
    if backend == "ref":
        fp = fingerprint(p2, backend=backend) if with_fingerprint else None
        q8, zeros, overflow = snapshot_fused_ref(jnp.ravel(p1), jnp.ravel(p2),
                                                 eps)
        if int(overflow) > 0:
            q, nz = delta_quantize(p1, p2, eps=eps, backend=backend)
            return q, nz, fp, False
        return jnp.asarray(q8).reshape(p1.shape), int(zeros), fp, True

    q, nz, ovf, pair = _snapshot_dev(
        _ZERO, p1, p2, eps=eps, interpret=_count("snapshot_fused", backend))
    nz, ovf, pair = jax.device_get((nz, ovf, pair))
    fp = None
    if with_fingerprint:
        # the fused pass hashes p2's float32 bits: that IS fingerprint(p2)
        # for float32 tensors; other dtypes hash their own bit width
        fp = (fold_fingerprint(p2, pair) if p2.dtype == jnp.float32
              else fingerprint(p2, backend=backend))
    if int(ovf) > 0:
        q, nz = delta_quantize(p1, p2, eps=eps, backend=backend)
        return q, nz, fp, False
    return q, int(nz), fp, True


def fold_fingerprint(x, pair) -> int:
    """Fold an (h1, h2) pair — from a kernel, the jnp oracle or the NumPy
    twin — into one int, salted with shape and dtype so reshaped or recast
    tensors don't alias (mirrors SHA-256 keying in the CAS)."""
    h1, h2 = (int(v) for v in np.asarray(pair))
    salt = hash((tuple(x.shape), str(x.dtype))) & 0xFFFFFFFF
    return ((h1 ^ salt) << 32) | h2


def fingerprint(x, backend: Optional[str] = None) -> int:
    """64-bit content fingerprint (python int) of ``x``'s values, shape and
    dtype."""
    backend = backend or default_backend()
    x = jnp.asarray(x)
    if backend == "ref":
        return fold_fingerprint(x, _fingerprint_ref(x))
    return fold_fingerprint(x, _fingerprint_dev(
        x, interpret=_count("fingerprint", backend)))


# ---------------------------------------------------------------------------
# device -> host copy
# ---------------------------------------------------------------------------

#: Traces of the copy's device view: one per (shape, dtype) in a process.
VIEW_TRACES = REGISTRY.group(
    "mgit_d2h_view", keys=("traces",),
    help="Traces of the device program that views a leaf as uint32 words")

#: Bytes of the leaf the view packs at a time: its temporaries stay this
#: small however large the leaf.
VIEW_BLOCK_BYTES = 8 << 20


def _words(part, width: int):
    """Each run of ``32 // width`` adjacent elements along the last axis of
    ``part`` as one uint32 word, the first in the low bits: the row-major
    bytes of ``part``, read as words, flattened."""
    pack = 32 // width
    bits = lax.bitcast_convert_type(part, jnp.dtype(f"uint{width}"))
    bits = bits.reshape(-1, bits.shape[-1])
    out = bits[:, 0::pack].astype(jnp.uint32)
    for j in range(1, pack):
        out = out | (bits[:, j::pack].astype(jnp.uint32) << (width * j))
    return out.reshape(-1)


@jax.jit
def _u32_view_dev(x):
    VIEW_TRACES.inc("traces")  # runs while tracing, not per call
    width = 8 * x.dtype.itemsize
    if x.ndim == 1:
        x = x.reshape(1, -1)
    # packing a large leaf in one go makes XLA stage whole copies of it;
    # a loop over blocks of its first axis writes each block's words in
    # place. A 2-D leaf's blocks are whole tiles of rows.
    lead, slab = x.shape[0], math.prod(x.shape[1:])
    align = 32 if x.ndim == 2 else 1
    step = VIEW_BLOCK_BYTES // (slab * x.dtype.itemsize) // align * align
    step = min(lead, max(align, step))
    n_steps, tail = divmod(lead, step)
    block_words = step * slab * width // 32

    def block(i, out):
        part = lax.dynamic_slice_in_dim(x, i * step, step)
        return lax.dynamic_update_slice_in_dim(
            out, _words(part, width), i * block_words, 0)

    out = lax.fori_loop(0, n_steps, block,
                        jnp.zeros((x.size * width // 32,), jnp.uint32))
    if tail:
        out = lax.dynamic_update_slice_in_dim(
            out, _words(x[n_steps * step:], width), n_steps * block_words, 0)
    return out


def _bits(dtype) -> int:
    """Bits of one element of a float or integer dtype, else 0."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).bits
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).bits
    return 0


def u32_view(x) -> Optional[np.ndarray]:
    """``x`` on the host, copied as a 1-D array of uint32 words.

    On a TPU a leaf's device layout is tiled, and a 16- or 8-bit leaf's
    tiles pack rows in pairs or quads, so a plain ``np.asarray`` has the
    runtime untile it on one host thread. One device pass packs each run
    of adjacent elements of a row into a word, into a 1-D array whose
    tiles are its row-major bytes; the host then only reinterprets them.
    The result is ``np.asarray(x)`` bit for bit. None where the elements
    do not pack whole into words: 64-bit or sub-byte dtypes, bool,
    complex, scalars, and a last axis the packing does not divide."""
    dtype = np.dtype(x.dtype)
    pack = 4 // dtype.itemsize if dtype.itemsize in (1, 2, 4) else 0
    if (not pack or _bits(dtype) != 8 * dtype.itemsize or x.ndim == 0
            or x.size == 0 or x.shape[-1] % pack):
        return None
    # the view's device buffer is dropped as soon as its words are here
    words = np.asarray(_u32_view_dev(x))
    return words.view(dtype).reshape(x.shape)


def _tiled(x) -> bool:
    """Whether ``x`` lives on one device whose layout is not the host's."""
    devices = x.devices()
    return len(devices) == 1 and next(iter(devices)).platform != "cpu"


def host_copy(x) -> Tuple[np.ndarray, str]:
    """``x`` as a row-major host array, and how it was copied: ``"u32"``
    through :func:`u32_view` for a ``jax.Array`` on one accelerator, else
    ``"asis"`` by ``np.asarray`` (NumPy and CPU arrays, leaves that do not
    pack into words)."""
    if isinstance(x, jax.Array) and _tiled(x):
        out = u32_view(x)
        if out is not None:
            return out, "u32"
    return np.asarray(x), "asis"


__all__ = ["delta_quantize", "dequant_apply", "chain_apply", "snapshot_fused",
           "fingerprint", "fold_fingerprint", "default_backend",
           "host_copy", "u32_view", "DISPATCHES", "VIEW_TRACES"]
