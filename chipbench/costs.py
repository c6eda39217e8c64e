"""Bytes each storage kernel moves and operations each train step needs,
computed from shapes.

A kernel call's bytes are the least it must move through HBM: its operands
read and its results written once, at their logical sizes (the padding of
the kernels' row-block layout and the per-block partials are left out).
``record_calls`` notes each call's shapes and dtypes through the public
wrappers of ``repro.kernels.ops`` while it is installed; it changes no
result.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, List

import numpy as np

KERNELS = ("snapshot_fused", "delta_quantize", "dequant_apply",
           "chain_apply", "fingerprint")


def kernel_bytes(kernel: str, n: int, *, itemsize: int = 4,
                 q_itemsize: int = 1, out_itemsize: int = 4,
                 hops: int = 1) -> int:
    """HBM bytes one call moves over ``n`` elements.

    snapshot_fused  p1, p2 read; q (int8) written
    delta_quantize  p1, p2 read; q (int32) written
    dequant_apply   p1 and q read; the tensor written
    chain_apply     base and ``hops`` deltas read; the tensor written
    fingerprint     the tensor read
    """
    if kernel == "snapshot_fused":
        return n * (2 * itemsize + 1)
    if kernel == "delta_quantize":
        return n * (2 * itemsize + 4)
    if kernel == "dequant_apply":
        return n * (itemsize + q_itemsize + out_itemsize)
    if kernel == "chain_apply":
        return n * (itemsize + hops * q_itemsize + out_itemsize)
    if kernel == "fingerprint":
        return n * itemsize
    raise KeyError(kernel)


def _size(x) -> int:
    return int(math.prod(np.shape(x)))


def _itemsize(x, default: int = 4) -> int:
    dt = getattr(x, "dtype", None)
    return int(np.dtype(dt).itemsize) if dt is not None else default


def _call_bytes(ops, kernel: str, args, kw) -> int:
    """Bytes of one wrapper call, from its arguments' shapes and dtypes."""
    x = args[0]
    n, size = _size(x), _itemsize(x)
    out = (int(np.dtype(kw["out_dtype"]).itemsize)
           if kw.get("out_dtype") is not None else size)
    if kernel == "dequant_apply":
        return kernel_bytes(kernel, n, itemsize=size,
                            q_itemsize=_itemsize(args[1]), out_itemsize=out)
    if kernel == "chain_apply":
        qs = args[1]
        wide = any(_itemsize(q) != 1 for q in qs)
        return kernel_bytes(kernel, n, itemsize=size, out_itemsize=out,
                            q_itemsize=4 if wide else 1, hops=len(qs))
    return kernel_bytes(kernel, n, itemsize=size)


@contextlib.contextmanager
def record_calls():
    """Yield ``{kernel: bytes}``, summed over every call made through the
    device backends while the block runs."""
    from repro.kernels import ops
    moved: Dict[str, int] = {k: 0 for k in KERNELS}
    orig = {k: getattr(ops, k) for k in KERNELS}

    def shim(kernel: str):
        def call(*args, **kw):
            if (kw.get("backend") or ops.default_backend()) != "ref":
                moved[kernel] += _call_bytes(ops, kernel, args, kw)
            return orig[kernel](*args, **kw)
        return call

    for k in KERNELS:
        setattr(ops, k, shim(k))
    try:
        yield moved
    finally:
        for k, f in orig.items():
            setattr(ops, k, f)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token (the
    embedding lookup does not; a separate output head does)."""
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    ffn = (3 if model.get("mlp_type") == "swiglu" else 2) * d * model["d_ff"]
    per_layer = d * hq + 2 * d * hkv + hq * d + ffn
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations per token: 6 per matmul parameter,
    plus 12 * layers * seq * (heads * head_dim) for the attention scores and
    their use, over the whole (unmasked) sequence as the program computes
    it. Recomputation under remat is not counted."""
    attn = 12 * model["n_layers"] * seq * model["n_heads"] * model["head_dim"]
    return 6.0 * matmul_params(model) + attn


def _width(dt) -> int:
    import jax.numpy as jnp
    return int(jnp.dtype(dt).itemsize)


def commit_bytes(leaves: List[Any]) -> int:
    """Bytes a commit of one derivative must move on the chip, whatever
    does it: parent and child read, an int8 delta written."""
    return sum((2 * _width(dt) + 1) * int(np.prod(shape))
               for _, shape, dt in leaves)


def checkout_bytes(leaves: List[Any], depth: int) -> int:
    """Bytes a checkout at chain depth ``depth`` must move: the base and
    ``depth`` int8 deltas read, the tensor written."""
    return sum((2 * _width(dt) + depth) * int(np.prod(shape))
               for _, shape, dt in leaves)
