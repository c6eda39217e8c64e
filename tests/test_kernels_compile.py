"""The storage kernels compile for a TPU v5e at qwen3-0.6b's real widths.

Nothing runs: the TPU compiler that ships with jaxlib compiles each jitted
device program of ``repro.kernels.ops`` for a described v5e chip, which
refuses what interpret mode accepts (tiles not aligned to (8, 128) or to
the int8 (32, 128) tile, scalar stores to VMEM, blocks that overrun VMEM).
The topology is described inside a fixture of this file only, so the one
test worker that gets this file is the one that loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
LEAF = (1024, 3072)          # one layer's MLP input weight (d_model, d_ff)
STACKED = (28, 1024, 3072)   # the same weight for all 28 layers, stacked
EMBED = (151936, 1024)       # the (tied) token embedding
SMALL = (128,)               # a head_dim-wide norm scale: below one tile
DEPTH = 8                    # ArtifactStore's max_chain_depth


@pytest.fixture(scope="module")
def one_chip():
    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
        env.undo()


def _programs(sds):
    zero = sds((1,), I32)
    eps = 1e-4
    return {
        "snapshot_fused": lambda shape: ops._snapshot_dev.lower(
            zero, sds(shape, F32), sds(shape, F32), eps=eps,
            interpret=False),
        "delta_quantize": lambda shape: ops._delta_quantize_dev.lower(
            zero, sds(shape, F32), sds(shape, F32), eps=eps,
            interpret=False),
        "dequant_apply": lambda shape: ops._dequant_apply_dev.lower(
            zero, sds(shape, F32), sds(shape, I8), eps=eps,
            out_dtype=jnp.dtype(F32), interpret=False),
        "dequant_apply_bf16": lambda shape: ops._dequant_apply_dev.lower(
            zero, sds(shape, BF16), sds(shape, I32), eps=eps,
            out_dtype=jnp.dtype(BF16), interpret=False),
        "fingerprint": lambda shape: ops._fingerprint_dev.lower(
            sds(shape, F32), interpret=False),
        "fingerprint_bf16": lambda shape: ops._fingerprint_dev.lower(
            sds(shape, BF16), interpret=False),
        "chain_apply": lambda shape: ops._chain_apply_dev.lower(
            zero, sds(shape, F32), tuple(sds(shape, I8) for _ in range(DEPTH)),
            eps=eps, out_dtype=jnp.dtype(F32), interpret=False),
        "chain_apply_int32": lambda shape: ops._chain_apply_dev.lower(
            zero, sds(shape, F32), tuple(sds(shape, I32)
                                         for _ in range(DEPTH)),
            eps=eps, out_dtype=jnp.dtype(F32), interpret=False),
    }


@pytest.mark.parametrize("program,shape", [
    ("snapshot_fused", LEAF), ("snapshot_fused", SMALL),
    ("delta_quantize", LEAF), ("delta_quantize", SMALL),
    ("dequant_apply", LEAF), ("dequant_apply", SMALL),
    ("dequant_apply_bf16", STACKED),
    ("fingerprint", LEAF), ("fingerprint", SMALL),
    ("fingerprint_bf16", EMBED),
    ("chain_apply", LEAF), ("chain_apply", SMALL),
    ("chain_apply_int32", LEAF),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v)
def test_kernel_compiles_for_v5e(one_chip, program, shape):
    def sds(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    compiled = _programs(sds)[program](shape).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA


@pytest.mark.parametrize("shape,dtype", [(EMBED, BF16), (STACKED, BF16),
                                         (LEAF, F32), (SMALL, I8)],
                         ids=["embed_bf16", "stacked_bf16", "leaf_f32",
                              "small_i8"])
def test_u32_view_compiles_for_v5e_without_staging_the_leaf(one_chip, shape,
                                                             dtype):
    """The device->host copy's word view compiles for a v5e and holds no
    more than a few row blocks besides its output: a view that staged the
    whole leaf, as a one-shot pack of a tiled 16-bit array does, would
    add the leaf's size again to a commit's peak device memory."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    mem = ops._u32_view_dev.lower(x).compile().memory_analysis()
    nbytes = x.size * jnp.dtype(dtype).itemsize
    assert nbytes <= mem.output_size_in_bytes <= max(nbytes, 4096)  # a tile
    assert mem.temp_size_in_bytes <= 4 * ops.VIEW_BLOCK_BYTES
