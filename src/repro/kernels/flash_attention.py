"""Pallas TPU kernel: flash attention forward (§Perf iteration 3).

Under XLA, chunked attention materializes every (qc x kc) score/prob tile to
HBM between the two dots — measured as the dominant memory term on all dense
prefill/train cells (e.g. deepseek prefill: 62L x 1024 steps x 100s-of-MB
tiles). This kernel keeps the tiles in VMEM: HBM traffic collapses to
q + out + n_q·(k + v) reads — the flash contract.

Layout: q (B, Hq, Sq, hd), k/v (B, Hkv, Skv, hd). Grid (B, Hq, n_q, n_k); the
last grid dim is sequential on TPU, so the output block (indexed by (b,h,qi),
constant over ki) accumulates across kv steps with VMEM scratch carrying the
online-softmax statistics. GQA folds into the k/v index map (h -> h // G).
Causal / sliding-window / prefix-LM masking is computed in-kernel from block
positions; fully-masked (future) blocks are skipped with @pl.when.

Forward only: serving (prefill/decode) needs no gradient, which is exactly
where the 32k-context cells live. Training keeps the XLA chunked path (bf16
score tiles); a custom-vjp flash backward is future work (DESIGN.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  qc: int, kc: int, n_k: int, causal: bool, window: int,
                  prefix_len: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * qc
    k_start = ki * kc
    # a block is live unless it is entirely in the causal future
    live = True
    if causal:
        live = k_start <= q_start + qc - 1

    @pl.when(live if isinstance(live, bool) else live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (qc, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (kc, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (qc, kc)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
        ok = jnp.ones((qc, kc), jnp.bool_)
        if causal:
            ok = k_pos <= q_pos
            if prefix_len > 0:
                ok = ok | (k_pos < prefix_len)
        if window > 0:
            ok = ok & (q_pos - k_pos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "prefix_len",
                                             "qc", "kc", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, prefix_len: int = 0,
                    qc: int = 512, kc: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Skv, hd) -> (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    qc = min(qc, Sq)
    while Sq % qc:
        qc -= 1
    kc = min(kc, Skv)
    while Skv % kc:
        kc -= 1
    n_q, n_k = Sq // qc, Skv // kc
    grid = (B, Hq, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, qc=qc, kc=kc, n_k=n_k, causal=causal, window=window,
        prefix_len=prefix_len, scale=hd ** -0.5)
    scratch = [pltpu.VMEM((qc,), jnp.float32),
               pltpu.VMEM((qc,), jnp.float32),
               pltpu.VMEM((qc, hd), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qc, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kc, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kc, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qc, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)


def flash_attention_ref(q, k, v, *, causal=True, window=0, prefix_len=0):
    """Dense jnp oracle (small shapes only)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * hd ** -0.5, kf)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    ok = jnp.ones((Sq, Skv), bool)
    if causal:
        ok = k_pos <= q_pos
        if prefix_len > 0:
            ok = ok | (k_pos < prefix_len)
    if window > 0:
        ok = ok & (q_pos - k_pos < window)
    s = jnp.where(ok[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def hbm_bytes(B, Hq, Hkv, Sq, Skv, hd, dtype_bytes=2, qc=512):
    """The kernel's HBM traffic contract (per the BlockSpecs): q and out once,
    k and v once per q block."""
    n_q = max(Sq // min(qc, Sq), 1)
    q_out = 2 * B * Hq * Sq * hd * dtype_bytes
    kv = 2 * B * Hkv * Skv * hd * dtype_bytes * n_q
    return q_out + kv
