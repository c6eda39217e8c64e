"""Plain reference for what a lineage checkout must return, bit for bit.

It imports nothing of the program. It restates MGit's storage semantics from
the paper (Algorithm 1) and the store's documented reconstruction truth
(DESIGN.md §10.2, §12), on the host in NumPy, which never contracts a
multiply and a subtract into one rounding:

* a derivative is stored against its parent's stored truth ``p`` as
  ``q = floor((p - c) * inv + 0.5)`` in float32, with ``inv`` the float32
  reciprocal of ``2 * log1p(eps)``, and its truth is ``p - q * scale``
  (each a correctly rounded float32 operation);
* a float32 tensor under ``CHUNK_THRESHOLD`` bytes folds a chain's
  same-eps hops: its truth is ``seg_base - (q_1 + ... + q_k) * scale`` with
  the exact int32 sum of the hops since the chain's full tensor;
* a tensor of ``CHUNK_THRESHOLD`` bytes or more is stored as chunks; its
  float32 hops apply one at a time, and a tensor of any other dtype is
  stored as its raw bytes, so its truth is the derivative itself;
* a smaller tensor of any other dtype takes one hop at a time, computed in
  float32 and rounded to its dtype.

``truth`` gives the stored truth of a child from its parent's; ``control``
gives the same in the next lower precision, the step that would tempt a
later change, and must disagree.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

#: Tensors of this many bytes or more go through the chunk layer.
CHUNK_THRESHOLD = 8 * 2 ** 20

#: A fold state: the segment's base value and the int32 sum of its hops.
Fold = Optional[Tuple[np.ndarray, np.ndarray]]


def scales(eps: float) -> Tuple[np.float32, np.float32]:
    scale = 2.0 * float(np.log1p(eps))
    return np.float32(scale), np.float32(1.0 / scale)


def quantize(parent: np.ndarray, child: np.ndarray, eps: float) -> np.ndarray:
    _, inv = scales(eps)
    d = parent.astype(np.float32) - child.astype(np.float32)
    return np.floor(d * inv + np.float32(0.5)).astype(np.int32)


def dequant(value: np.ndarray, q: np.ndarray, eps: float) -> np.ndarray:
    scale, _ = scales(eps)
    return value.astype(np.float32) - q.astype(np.float32) * scale


def truth(parent: np.ndarray, fold: Fold, child: np.ndarray, eps: float
          ) -> Tuple[np.ndarray, Fold]:
    """Stored truth of ``child`` committed against a parent whose truth is
    ``parent`` (with fold state ``fold``), and the child's fold state."""
    dtype = child.dtype
    if child.nbytes >= CHUNK_THRESHOLD:
        if dtype != np.float32:
            return child, None
        return dequant(parent, quantize(parent, child, eps), eps), None
    q = quantize(parent, child, eps)
    if dtype != np.float32:
        return dequant(parent, q, eps).astype(dtype), None
    base, qsum = fold if fold is not None else (parent, np.zeros_like(q))
    qsum = qsum + q
    return dequant(base, qsum, eps), (base, qsum)


def control(parent: np.ndarray, fold: Fold, child: np.ndarray, eps: float
            ) -> np.ndarray:
    """``truth`` computed one precision lower: bfloat16 for a float32
    configuration, float8 (e4m3) for a bfloat16 one."""
    import ml_dtypes
    low = (ml_dtypes.bfloat16 if child.dtype == np.float32
           else ml_dtypes.float8_e4m3fn)
    scale, _ = scales(eps)
    if child.nbytes >= CHUNK_THRESHOLD and child.dtype != np.float32:
        return child.astype(low).astype(child.dtype)
    q = quantize(parent, child, eps)
    base, qsum = ((parent, q) if fold is None or child.nbytes
                  >= CHUNK_THRESHOLD or child.dtype != np.float32
                  else (fold[0], fold[1] + q))
    out = base.astype(low) - (qsum.astype(np.float32) * scale).astype(low)
    return out.astype(child.dtype)


def mismatches(a: np.ndarray, b: np.ndarray) -> int:
    """Elements whose bits differ (every element, where shape or dtype
    differ)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return max(a.size, b.size)
    w = np.dtype(f"u{a.dtype.itemsize}")
    return int(np.count_nonzero(np.ascontiguousarray(a).view(w)
                                != np.ascontiguousarray(b).view(w)))
