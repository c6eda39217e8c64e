"""The on-device G2 finetune generator keeps its stated statistics."""

import numpy as np

from helpers import ROOT  # noqa: F401

import jax

from chipbench import generate


def _model(dtype="float32"):
    return {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
            "head_dim": 64, "d_ff": 512, "vocab_size": 1000,
            "mlp_type": "gelu", "dtype": dtype}


def test_leaf_specs_one_tensor_per_layer_in_key_order():
    specs = generate.leaf_specs(_model())
    keys = [k for k, _, _ in specs]
    assert keys == sorted(keys)
    assert ("layers/01/attn/wk", (256, 128), "float32") in specs
    assert ("lm_head", (256, 1000), "float32") in specs
    assert len(specs) == 3 + 2 * 8


def test_finetune_statistics_match_the_stated_parameters():
    specs = generate.leaf_specs(_model())
    base = generate.base_weights(specs, 2 ** 31 + 5)
    ft = generate.finetune(base, 2 ** 31 + 5, 1, density=0.1, scale=5e-5,
                           freeze_frac=0.3)
    keys = sorted(base)
    n_frozen = int(len(keys) * 0.3)
    moved = total = 0
    deltas = []
    for i, k in enumerate(keys):
        a, b = np.asarray(base[k]), np.asarray(ft[k])
        if i < n_frozen:
            assert np.array_equal(a, b), k        # frozen leaves: bit-equal
            continue
        d = (b.astype(np.float64) - a.astype(np.float64)).ravel()
        moved += np.count_nonzero(d)
        total += d.size
        deltas.append(d[d != 0])
    density = moved / total
    assert abs(density - 0.1) < 0.005, density
    std = np.concatenate(deltas).std()
    assert abs(std - 5e-5) / 5e-5 < 0.05, std


def test_same_seed_same_bits_and_large_seeds_differ():
    specs = generate.leaf_specs(_model("bfloat16"))
    a = generate.base_weights(specs, 2 ** 31 + 7)
    b = generate.base_weights(specs, 2 ** 31 + 7)
    c = generate.base_weights(specs, 2 ** 31 + 7 + 2 ** 32)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not all(np.array_equal(np.asarray(a[k]), np.asarray(c[k]))
                   for k in a)
    assert a["lm_head"].dtype == jax.numpy.bfloat16
