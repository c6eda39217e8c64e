"""Pallas TPU kernel: FUSED checkpoint snapshot pass (§Perf-C optimization).

The paper's storage pipeline runs, per checkpoint tensor:
    (1) delta_quantize(p_prev, p_new)   reads p_prev, p_new; writes q (int32)
    (2) fingerprint(p_new)              reads p_new again
i.e. 16 bytes of HBM traffic per fp32 parameter. This kernel fuses both into
ONE streaming pass and narrows q to int8 (training-step deltas quantize to
tiny integers; a per-tile overflow count routes rare wide tensors to the
int32 fallback):

    traffic per param: 4 (p_prev) + 4 (p_new) + 1 (q int8) = 9 bytes -> 1.78x
    less HBM time on the checkpoint hot path, plus a 4x smaller buffer for
    the host's lossless codec.

Outputs per tile: q (int8) plus (8, 128) partials of the zero count, the
overflow count and the two fingerprint halves. All tile-decomposable; XLA
sums the partials after the call (see ``delta_quantize`` for why they are
not reduced to scalars in the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.delta_quantize import (BLOCK_ROWS, ZERO_SPEC,
                                          partial_shape, partial_spec,
                                          rounded, tile_partial_sum)
from repro.kernels.fingerprint import tile_fingerprint
from repro.kernels.ref import inv_quant_scale


def _snapshot_kernel(zero_ref, p1_ref, p2_ref, q_ref, zeros_ref, ovf_ref,
                     h1_ref, h2_ref, *, inv_scale: float, n: int):
    p2 = p2_ref[...]
    # --- delta + quantize + int8 narrowing -------------------------------
    t = rounded((p1_ref[...] - p2) * inv_scale, zero_ref)
    q32 = jnp.floor(t + 0.5).astype(jnp.int32)
    q8 = jnp.clip(q32, -127, 127)
    q_ref[...] = q8.astype(jnp.int8)
    zeros_ref[...] = tile_partial_sum((q32 == 0).astype(jnp.int32))
    ovf_ref[...] = tile_partial_sum((q32 != q8).astype(jnp.int32))
    # --- fingerprint of p2 (the new params), same mix as fingerprint.py --
    h1_ref[...], h2_ref[...] = tile_fingerprint(
        jax.lax.bitcast_convert_type(p2, jnp.uint32), pl.program_id(0), n)


@functools.partial(jax.jit,
                   static_argnames=("n", "eps", "block_rows", "interpret"))
def snapshot_fused_2d(zero: jnp.ndarray, p1: jnp.ndarray, p2: jnp.ndarray,
                      n: int, eps: float = 1e-4, block_rows: int = BLOCK_ROWS,
                      interpret: bool = False):
    """zero: (1,) int32 holding 0 (see ``delta_quantize``);
    p1 (prev), p2 (new): (rows, cols) f32, rows % block_rows == 0,
    block_rows % 32 == 0 (the int8 tile), holding ``n`` real elements.

    Returns (q int8, then (8 * n_blocks, 128) partials of the zero count,
    the overflow count, fingerprint h1 and fingerprint h2).
    """
    rows, cols = p1.shape
    grid = (rows // block_rows,)
    kernel = functools.partial(_snapshot_kernel,
                               inv_scale=float(inv_quant_scale(eps)), n=n)
    tile = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[ZERO_SPEC, tile, tile],
        out_specs=[tile] + [partial_spec()] * 4,
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.int8),
                   partial_shape(grid[0], jnp.int32),
                   partial_shape(grid[0], jnp.int32),
                   partial_shape(grid[0], jnp.uint32),
                   partial_shape(grid[0], jnp.uint32)],
        interpret=interpret,
        name="snapshot_fused",
    )(zero, p1, p2)


def snapshot_fused_ref(p1: jnp.ndarray, p2: jnp.ndarray, eps: float = 1e-4):
    """jnp oracle with identical semantics (flat tensors of any shape)."""
    from repro.kernels import ref as _ref
    q32, _ = _ref.delta_quantize_ref(p1, p2, eps)
    q8 = jnp.clip(q32, -127, 127).astype(jnp.int8)
    overflow = jnp.sum(q32 != q8.astype(jnp.int32), dtype=jnp.int32)
    zeros = jnp.sum(q32 == 0, dtype=jnp.int32)
    return q8, zeros, overflow


__all__ = ["snapshot_fused_2d", "snapshot_fused_ref"]
