"""Pallas kernels vs jnp oracles: shape/dtype sweep, interpret=True on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.ref import fingerprint_host, quant_scale

SHAPES = [(8,), (100,), (128, 128), (257, 33), (1024,), (3, 5, 7),
          (2048, 128), (1, 1)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_delta_quantize_kernel_matches_oracle(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    p2 = jnp.asarray(rng.normal(size=shape), dtype=dtype)
    p1 = p2 + jnp.asarray(rng.normal(scale=1e-4, size=shape), dtype=dtype)
    q_ref, nz_ref = ops.delta_quantize(p1, p2, backend="ref")
    q_pal, nz_pal = ops.delta_quantize(p1, p2, backend="interpret")
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_pal))
    assert nz_ref == nz_pal


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_dequant_apply_kernel_matches_oracle(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    p1 = jnp.asarray(rng.normal(size=shape), dtype=dtype)
    q = jnp.asarray(rng.integers(-100, 100, size=shape), dtype=jnp.int32)
    out_ref = ops.dequant_apply(p1, q, backend="ref")
    out_pal = ops.dequant_apply(p1, q, backend="interpret")
    # bit-identical: the kernel rounds q*scale before subtracting, as the
    # oracle does, even where the compiler would fuse them into an FMA
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


@pytest.mark.parametrize("shape", [(100,), (257, 33), (128, 128), (3, 5, 7)])
@pytest.mark.parametrize("k", [1, 3, 6])
def test_chain_apply_kernel_matches_oracle(shape, k):
    """Fused chain-apply == base - sum(q)*scale (DESIGN.md §10.2)."""
    rng = np.random.default_rng(hash((shape, k)) % 2**31)
    base = jnp.asarray(rng.normal(size=shape), dtype=jnp.float32)
    qs = [rng.integers(-100, 100, size=shape).astype(np.int8)
          for _ in range(k)]
    out_ref = ops.chain_apply(base, qs, backend="ref")
    out_pal = ops.chain_apply(base, qs, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))
    # the fold identity vs single dequant of the exact int32 sum
    qsum = np.zeros(shape, np.int32)
    for q in qs:
        qsum += q
    single = ops.dequant_apply(base, qsum, backend="ref",
                               out_dtype="float32")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(single))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES + [jnp.int32], ids=str)
def test_fingerprint_kernel_matches_oracle(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    if dtype == jnp.int32:
        x = jnp.asarray(rng.integers(-1000, 1000, size=shape), dtype)
    else:
        x = jnp.asarray(rng.normal(size=shape), dtype)
    fp = ops.fingerprint(x, backend="ref")
    assert fp == ops.fingerprint(x, backend="interpret")
    assert fp == ops.fold_fingerprint(x, fingerprint_host(np.asarray(x)))


def test_fingerprint_sensitivity():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 256)),
                    jnp.float32)
    f0 = ops.fingerprint(x, backend="ref")
    y = x.at[13, 200].add(1e-6)
    assert ops.fingerprint(y, backend="ref") != f0          # value change
    assert ops.fingerprint(x.reshape(128, 512), backend="ref") != f0  # shape salt
    assert ops.fingerprint(x, backend="ref") == f0          # deterministic


@given(scale=st.floats(1e-6, 1e-2), eps=st.sampled_from([1e-5, 1e-4, 1e-3]))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bound(scale, eps):
    """|dequant(quant(p1-p2)) - (p1-p2)| <= quant step / 2 (+ float eps)."""
    rng = np.random.default_rng(0)
    p2 = rng.normal(size=(500,)).astype(np.float32)
    p1 = (p2 + rng.normal(scale=scale, size=(500,))).astype(np.float32)
    q, _ = ops.delta_quantize(p1, p2, eps=eps, backend="ref")
    rec = np.asarray(ops.dequant_apply(p1, q, eps=eps, backend="ref"))
    assert np.max(np.abs(rec - p2)) <= quant_scale(eps) * 0.51 + 1e-6


def test_zero_stats_prefilter():
    p2 = np.zeros(4096, np.float32)
    p1 = p2.copy()
    p1[:64] += 1.0
    q, nz, blocks = ops.delta_quantize(jnp.asarray(p1), jnp.asarray(p2),
                                       backend="interpret",
                                       return_block_zeros=True)
    assert nz == 4096 - 64
    assert blocks is not None and int(np.sum(blocks)) >= nz


# ---------------------------------------------------------------------------
# fused snapshot kernel (§Perf-C)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(100,), (256, 1024), (257, 33)])
def test_snapshot_fused_matches_unfused(shape):
    rng = np.random.default_rng(hash(shape) % 2**31)
    p2 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    p1 = p2 + jnp.asarray(rng.normal(scale=1e-5, size=shape), jnp.float32)
    q_f, nz_f, fp_f, narrow = ops.snapshot_fused(p1, p2, backend="ref")
    q_u, nz_u = ops.delta_quantize(p1, p2, backend="ref")
    assert narrow  # tiny deltas always fit int8
    np.testing.assert_array_equal(np.asarray(q_f, np.int32), np.asarray(q_u))
    assert nz_f == nz_u
    assert fp_f == ops.fingerprint(p2, backend="ref")


@pytest.mark.parametrize("shape", [(100,), (256, 1024)])
def test_snapshot_fused_interpret_parity(shape):
    rng = np.random.default_rng(0)
    p2 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    p1 = p2 + jnp.asarray(rng.normal(scale=1e-5, size=shape), jnp.float32)
    q_r, nz_r, fp_r, na_r = ops.snapshot_fused(p1, p2, backend="ref")
    q_i, nz_i, fp_i, na_i = ops.snapshot_fused(p1, p2, backend="interpret")
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_i))
    assert (nz_r, fp_r, na_r) == (nz_i, fp_i, na_i)


def test_snapshot_fused_overflow_fallback():
    p2 = jnp.zeros(1000, jnp.float32)
    p1 = p2.at[3].set(1.0)  # delta / 2e-4 = 5000 >> int8
    q, nz, fp, narrow = ops.snapshot_fused(p1, p2, backend="ref")
    assert not narrow
    assert q.dtype == jnp.int32
    assert int(q[3]) > 127


# ---------------------------------------------------------------------------
# flash attention kernel (§Perf iteration 3) — interpret vs dense oracle
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention import flash_attention, flash_attention_ref


@pytest.mark.parametrize("spec", [
    dict(B=2, Hq=4, Hkv=2, S=64, hd=16, causal=True),
    dict(B=1, Hq=8, Hkv=1, S=32, hd=8, causal=True),          # MQA
    dict(B=2, Hq=4, Hkv=4, S=64, hd=16, causal=True, window=24),
    dict(B=1, Hq=4, Hkv=2, S=48, hd=16, causal=True, prefix_len=16),
    dict(B=2, Hq=2, Hkv=2, S=64, hd=16, causal=False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_flash_attention_matches_oracle(spec, dtype):
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, hd = (spec[k] for k in ("B", "Hq", "Hkv", "S", "hd"))
    kw = {k: spec[k] for k in ("causal", "window", "prefix_len") if k in spec}
    q = jnp.asarray(rng.normal(size=(B, Hq, S, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), dtype)
    out = flash_attention(q, k, v, qc=16, kc=16, interpret=True, **kw)
    ref = flash_attention_ref(q, k, v, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_hbm_contract():
    from repro.kernels.flash_attention import hbm_bytes
    # q+out once, k+v per q block
    b = hbm_bytes(B=1, Hq=4, Hkv=2, Sq=1024, Skv=1024, hd=64, qc=512)
    assert b == (2 * 1 * 4 * 1024 * 64 * 2) + 2 * (2 * 1 * 2 * 1024 * 64 * 2)
