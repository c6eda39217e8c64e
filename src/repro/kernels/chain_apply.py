"""Pallas TPU kernel: fused delta-chain application (DESIGN.md §10.2).

Checkout of a depth-k delta chain reduces, for same-eps float32 segments, to

    out = base - (q_1 + q_2 + ... + q_k) * scale

because dequant is linear in q at fixed eps and int32 sums are exact. Done
hop-by-hop that is k full HBM round-trips of the (tensor-sized) intermediate
value: 12k bytes of traffic per fp32 param. This kernel fuses the whole
segment into ONE streaming pass — each row block reads its base tile once,
streams the k quantized-delta tiles through an int32 VMEM accumulator (an
"arbitrary" grid axis over k), and writes one output tile:

    traffic per param: 4 (base) + k * itemsize(q) + 4 (out) vs 12k
    hop-by-hop; narrowed int8 hops stream at 1 byte each, and no
    intermediate tensor ever exists in HBM.

Streaming over k keeps VMEM use independent of depth (two q tiles, the base
and output tiles and the accumulator), so every depth up to
``max_chain_depth`` compiles at full block size. ``eps`` is compile-time for
the same reason as ``delta_quantize``.

Layout matches the other storage kernels: tensors are flattened and padded
to (rows, LANE_COLS); the q stack is (k, rows, cols).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.delta_quantize import BLOCK_ROWS, ZERO_SPEC, rounded
from repro.kernels.ref import quant_scale


def _chain_apply_kernel(zero_ref, base_ref, q_ref, out_ref, acc_ref, *,
                        scale: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += q_ref[...].astype(jnp.int32)  # exact int32 sum

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = base_ref[...] - rounded(
            acc_ref[...].astype(jnp.float32) * scale, zero_ref)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def chain_apply_2d(zero: jnp.ndarray, base: jnp.ndarray, qs: jnp.ndarray,
                   eps: float = 1e-4, block_rows: int = BLOCK_ROWS,
                   interpret: bool = False):
    """zero: (1,) int32 holding 0 (see ``delta_quantize``); base: (rows,
    cols) f32; qs: (k, rows, cols) int32/int8.

    rows % block_rows == 0, block_rows % 32 == 0, cols % 128 == 0. Returns
    f32 (rows, cols): ``base - sum_k(qs) * scale`` in one fused pass.
    """
    rows, cols = base.shape
    grid = (rows // block_rows, qs.shape[0])
    kernel = functools.partial(_chain_apply_kernel,
                               scale=float(quant_scale(eps)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            ZERO_SPEC,
            pl.BlockSpec((block_rows, cols), lambda i, k: (i, 0)),
            pl.BlockSpec((None, block_rows, cols), lambda i, k: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_rows, cols), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="chain_apply",
    )(zero, base, qs)


def chain_apply_ref(base: jnp.ndarray, qs: jnp.ndarray,
                    eps: float = 1e-4) -> jnp.ndarray:
    """jnp oracle with identical semantics (any matching shapes)."""
    total = jnp.sum(jnp.asarray(qs, dtype=jnp.int32), axis=0)
    return (jnp.asarray(base, dtype=jnp.float32)
            - total.astype(jnp.float32) * quant_scale(eps))


__all__ = ["chain_apply_2d", "chain_apply_ref"]
