"""Pallas TPU kernel: fused delta + quantize (Algorithm 1's lossy step).

The storage hot path runs `floor((p1 - p2) * inv_scale + 0.5)` over every
parameter of every checkpoint. Arithmetic intensity is ~3 FLOPs / 12 bytes ≈
0.25 — firmly HBM-bandwidth bound — so the only thing that matters is touching
each byte exactly once: one fused pass, no intermediate Δp materialized in HBM.

The kernel additionally emits per-tile zero counts. The host uses these
counts to *pre-filter* tensors for lossless compression (predicted ratio <= 1
→ don't ship the tensor to the host compressor), which is the paper's
"reject if no saving" check pushed down to the device.

Rounding: the NumPy twins round every product before the add that follows
it. A compiler that contracts the pair into an FMA skips that rounding
(XLA:CPU does, so interpret mode would), so each product passes through
:func:`rounded` first, which no compiler can see through. Every kernel then
gives the twins' bits on every backend.

Layout: inputs are flattened and padded to (rows, LANE_COLS) where LANE_COLS
is a multiple of 128 (TPU lane width) and rows a multiple of the block. Grid
is 1-D over row-blocks; each program reads two (block_rows, LANE_COLS) VMEM
tiles and writes one int32 tile plus one (8, 128) partial-count tile. Per-
tile counts are never reduced to a scalar inside the kernel: Mosaic cannot
store scalars to VMEM, and an (8, 128) int32 block is exactly one vreg, so
the partials stay tile-aligned and XLA sums them after the call (integer
sums are exact in any order). ``eps`` (hence the scale) is a compile-time
constant — it is a per-lineage-graph config value, so specializing the
kernel on it costs one compile per distinct eps and saves a scalar operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import inv_quant_scale, quant_scale

# 8 sublanes x 128 lanes is the float32 VREG tile; 256x1024 keeps VMEM use
# ~3 MB for (p1, p2, q) while giving the DMA engine long contiguous reads.
BLOCK_ROWS = 256
LANE_COLS = 1024
# int8 tiles are (32, 128): every block (and so every padded row count) is a
# multiple of 32 rows, so the narrowed q of ``snapshot_fused`` tiles too
MIN_BLOCK_ROWS = 32
# one vreg: the per-tile partial-sum block every reducing kernel writes
PARTIAL = (8, 128)


def tile_partial_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Fold a (rows, cols) integer tile into one (8, 128) partial-sum tile.

    Adds sublane groups, then lane groups — elementwise vreg adds only, no
    cross-lane reduction. Integer adds (uint32 wrapping) are associative,
    so the total of every partial equals the tile's sum exactly."""
    rows, cols = x.shape
    acc = x[0:8]
    for r in range(8, rows, 8):
        acc = acc + x[r:r + 8]
    out = acc[:, 0:128]
    for c in range(128, cols, 128):
        out = out + acc[:, c:c + 128]
    return out


#: the runtime int32 zero every rounding kernel takes as its first operand
ZERO_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def rounded(x: jnp.ndarray, zero_ref) -> jnp.ndarray:
    """``x`` rounded to float32 where it stands.

    ``zero_ref`` holds a 0 that is only known at run time: XOR-ing the bits
    with it is an identity no compiler can prove, so ``x`` cannot be fused
    into an FMA with the add that consumes it."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) ^ zero_ref[0]
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def partial_spec() -> pl.BlockSpec:
    return pl.BlockSpec(PARTIAL, lambda i: (i, 0))


def partial_shape(n_blocks: int, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n_blocks * PARTIAL[0], PARTIAL[1]), dtype)


def _delta_quantize_kernel(zero_ref, p1_ref, p2_ref, q_ref, zeros_ref, *,
                           inv_scale: float):
    d = p1_ref[...] - p2_ref[...]
    q = jnp.floor(rounded(d * inv_scale, zero_ref) + 0.5).astype(jnp.int32)
    q_ref[...] = q
    zeros_ref[...] = tile_partial_sum((q == 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def delta_quantize_2d(zero: jnp.ndarray, p1: jnp.ndarray, p2: jnp.ndarray,
                      eps: float = 1e-4, block_rows: int = BLOCK_ROWS,
                      interpret: bool = False):
    """zero: (1,) int32 holding 0, passed in from outside every jit;
    p1, p2: (rows, cols) float32 with rows % block_rows == 0,
    block_rows % 32 == 0, cols % 128 == 0.

    Returns (q int32 (rows, cols), zero-count partials (8 * n_blocks, 128)).
    """
    rows, cols = p1.shape
    grid = (rows // block_rows,)
    kernel = functools.partial(_delta_quantize_kernel,
                               inv_scale=float(inv_quant_scale(eps)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            ZERO_SPEC,
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            partial_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
            partial_shape(grid[0], jnp.int32),
        ],
        interpret=interpret,
        name="delta_quantize",
    )(zero, p1, p2)


def _dequant_apply_kernel(zero_ref, p1_ref, q_ref, out_ref, *, scale: float):
    out_ref[...] = p1_ref[...] - rounded(
        q_ref[...].astype(jnp.float32) * scale, zero_ref)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def dequant_apply_2d(zero: jnp.ndarray, p1: jnp.ndarray, q: jnp.ndarray,
                     eps: float = 1e-4, block_rows: int = BLOCK_ROWS,
                     interpret: bool = False):
    """Reconstruct child tile-wise: p2' = p1 - q * scale (float32 out).

    p1 float32, q int8 or int32 (int8 stays narrow across the link)."""
    rows, cols = p1.shape
    grid = (rows // block_rows,)
    kernel = functools.partial(_dequant_apply_kernel,
                               scale=float(quant_scale(eps)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            ZERO_SPEC,
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
        name="dequant_apply",
    )(zero, p1, q)


__all__ = ["delta_quantize_2d", "dequant_apply_2d", "tile_partial_sum",
           "rounded", "ZERO_SPEC",
           "BLOCK_ROWS", "LANE_COLS", "MIN_BLOCK_ROWS", "quant_scale"]
