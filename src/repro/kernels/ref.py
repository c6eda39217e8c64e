"""Pure-jnp oracles for the storage-path kernels.

These define the EXACT semantics the Pallas kernels must reproduce (tests
assert exact equality across shape & dtype sweeps). They are also the
runtime implementation on CPU hosts, where Pallas would only run in interpret
mode (slow); ``ops.py`` dispatches. ``_mix`` and :func:`fingerprint_host`
use only wrapping uint32 operators, so the same code runs on NumPy arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Quantization scale for error bound eps (paper §4): Δq = floor(Δp / (2·log1p(eps)) + 0.5)
def quant_scale(eps: float) -> float:
    return 2.0 * float(np.log1p(eps))


def inv_quant_scale(eps: float) -> np.float32:
    """The float32 reciprocal every quantizer multiplies by.

    Division is not correctly rounded on every backend (a TPU divides by
    reciprocal and refinement), while an f32 multiply is; so the kernels,
    this oracle and the NumPy twin all quantize as ``d * inv_quant_scale``
    and agree bit-for-bit wherever they run."""
    return np.float32(1.0 / quant_scale(eps))


def delta_quantize_ref(p1: jnp.ndarray, p2: jnp.ndarray, eps: float = 1e-4):
    """Quantized delta between parent p1 and child p2 (paper Algorithm 1).

    Returns (q int32 array, zero count). Computation is in float32 regardless
    of input dtype so bf16 checkpoints quantize identically to f32 ones.
    """
    d = p1.astype(jnp.float32) - p2.astype(jnp.float32)
    q = jnp.floor(d * inv_quant_scale(eps) + 0.5).astype(jnp.int32)
    return q, jnp.sum(q == 0, dtype=jnp.int32)


def dequant_apply_ref(p1: jnp.ndarray, q: jnp.ndarray, eps: float = 1e-4,
                      out_dtype=None) -> jnp.ndarray:
    """Reconstruct the child: p2' = p1 - dequantize(q)."""
    scale = quant_scale(eps)
    out = p1.astype(jnp.float32) - q.astype(jnp.float32) * scale
    return out.astype(out_dtype or p1.dtype)


# -- fingerprint -------------------------------------------------------------
# Order-sensitive 2x32-bit mixing hash: each element is mixed with its global
# position, partial sums wrap mod 2^32. Sum-combining makes the hash
# tile-decomposable (any tiling yields the same result), which is what lets
# the Pallas kernel parallelize over VMEM tiles and tree-combine.
FP_C1 = np.uint32(0x9E3779B1)  # golden-ratio constant
FP_C2 = np.uint32(0x85EBCA77)
FP_C3 = np.uint32(0xC2B2AE3D)


def _mix(bits: jnp.ndarray, idx: jnp.ndarray):
    x = (bits * FP_C1) ^ (idx * FP_C2)
    x = x * FP_C3
    h1 = x ^ (x >> 15)
    y = (bits + idx) * FP_C2
    h2 = y ^ (y >> 13)
    return h1, h2


def fingerprint_bits(x: jnp.ndarray) -> jnp.ndarray:
    """The flat uint32 stream a fingerprint hashes: raw bits of 32-bit
    values, zero-extended bits of 16-bit floats, f32 bits otherwise."""
    flat = jnp.ravel(x)
    if flat.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if flat.dtype == jnp.bfloat16 or flat.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    if flat.dtype in (jnp.int32, jnp.uint32):
        return flat.astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(flat.astype(jnp.float32), jnp.uint32)


def fingerprint_ref(x: jnp.ndarray) -> jnp.ndarray:
    """64-bit content fingerprint as a (2,) uint32 array [h1, h2]."""
    bits = fingerprint_bits(x)
    idx = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    h1, h2 = _mix(bits, idx)
    return jnp.stack([jnp.sum(h1, dtype=jnp.uint32), jnp.sum(h2, dtype=jnp.uint32)])


def fingerprint_host(x: np.ndarray) -> np.ndarray:
    """NumPy twin of :func:`fingerprint_ref`: the same (2,) uint32 pair,
    computed on the host with no JAX dispatch."""
    flat = np.ravel(np.asarray(x))
    if flat.dtype == np.float32 or flat.dtype == np.int32:
        bits = flat.view(np.uint32)
    elif flat.dtype == np.uint32:
        bits = flat
    elif flat.dtype == jnp.bfloat16 or flat.dtype == np.float16:
        bits = flat.view(np.uint16).astype(np.uint32)
    else:
        bits = flat.astype(np.float32).view(np.uint32)
    idx = np.arange(flat.size, dtype=np.uint32)
    h1, h2 = _mix(bits, idx)
    return np.array([h1.sum(dtype=np.uint32), h2.sum(dtype=np.uint32)])
