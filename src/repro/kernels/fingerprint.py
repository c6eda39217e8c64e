"""Pallas TPU kernel: content fingerprint for on-device dedup candidate detection.

SHA-256 (the paper's durable key) is byte-serial — no TPU mapping. The TPU
adaptation (DESIGN.md §3) computes a position-mixed 2x32-bit hash whose
partial sums wrap mod 2^32, making it *tile-decomposable*: any tiling of the
tensor produces identical results, so the kernel parallelizes freely over
VMEM tiles and XLA tree-combines the per-tile partials.

Use: right after an optimizer step / checkpoint cut, fingerprint every
parameter on-device. Only tensors whose fingerprint is NOT already in the CAS
index need a host transfer + SHA-256; frozen/shared tensors (the paper's G1,
G5 regimes: up to 80% duplicates) never leave HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.delta_quantize import (BLOCK_ROWS, partial_shape,
                                          partial_spec, tile_partial_sum)
from repro.kernels.ref import _mix


def tile_fingerprint(bits: jnp.ndarray, block: jnp.ndarray, n: int):
    """(8, 128) uint32 partials (h1, h2) of one (block_rows, cols) tile of
    the flat uint32 stream, for grid program ``block``. Elements at flat
    index >= ``n`` are padding and contribute nothing, so the result does
    not depend on the canonical layout."""
    rows, cols = bits.shape
    base = (block * (rows * cols)).astype(jnp.uint32)
    row = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 1)
    idx = base + row * jnp.uint32(cols) + col
    h1, h2 = _mix(bits, idx)
    valid = idx < jnp.uint32(n)
    zero = jnp.zeros_like(h1)
    return (tile_partial_sum(jnp.where(valid, h1, zero)),
            tile_partial_sum(jnp.where(valid, h2, zero)))


def _fingerprint_kernel(bits_ref, h1_ref, h2_ref, *, n: int):
    h1_ref[...], h2_ref[...] = tile_fingerprint(bits_ref[...],
                                                pl.program_id(0), n)


@functools.partial(jax.jit, static_argnames=("n", "block_rows", "interpret"))
def fingerprint_2d(bits: jnp.ndarray, n: int, block_rows: int = BLOCK_ROWS,
                   interpret: bool = False) -> jnp.ndarray:
    """bits: (rows, cols) uint32, rows % block_rows == 0, holding ``n``
    real elements (the rest is padding). Returns (2,) uint32.

    Per-tile partials are written to two (8 * n_blocks, 128) buffers and
    wrap-summed — the combine is associative/commutative so the reduction
    order is free.
    """
    rows, cols = bits.shape
    grid = (rows // block_rows,)
    kernel = functools.partial(_fingerprint_kernel, n=n)
    h1, h2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=[partial_spec(), partial_spec()],
        out_shape=[partial_shape(grid[0], jnp.uint32),
                   partial_shape(grid[0], jnp.uint32)],
        interpret=interpret,
        name="fingerprint",
    )(bits)
    return jnp.stack([jnp.sum(h1, dtype=jnp.uint32),
                      jnp.sum(h2, dtype=jnp.uint32)])


__all__ = ["fingerprint_2d", "tile_fingerprint"]
