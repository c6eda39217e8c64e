"""Weights and derivatives made on the device from ``--seed``.

Lineage cells store a model the way published checkpoints do: one tensor per
layer. ``leaf_specs`` lays a configuration's sizes out so; ``base_weights``
makes every leaf in one jitted call; ``finetune`` makes one derivative in one
jitted call, with the update statistics of the MGit paper's G2 version chains
(Table 3): a ``density`` share of the elements of every unfrozen leaf moves
by Normal(0, ``scale``), and the first ``freeze_frac`` of the leaves, in key
order, stay bit for bit as they were (G1's frozen trunk).

The same seed gives the same bits: every random draw is keyed by the seed and
the leaf's index in key order.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[str, Tuple[int, ...], str]


def root_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it, the rest
    are folded in, so seeds above 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_specs(model: Dict) -> List[Spec]:
    """(key, shape, dtype) of every tensor, one per layer, in key order."""
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv, ff = model["n_heads"] * hd, model["n_kv_heads"] * hd, model["d_ff"]
    dt = model["dtype"]
    specs: List[Spec] = [("embed/tok", (model["vocab_size"], d), dt),
                         ("final_norm", (d,), dt)]
    if not model.get("tie_embeddings", False):
        specs.append(("lm_head", (d, model["vocab_size"]), dt))
    for i in range(model["n_layers"]):
        p = f"layers/{i:02d}/"
        specs += [(p + "attn/wq", (d, hq), dt), (p + "attn/wk", (d, hkv), dt),
                  (p + "attn/wv", (d, hkv), dt), (p + "attn/wo", (hq, d), dt),
                  (p + "ln1", (d,), dt), (p + "ln2", (d,), dt),
                  (p + "mlp/w_in", (d, ff), dt),
                  (p + "mlp/w_out", (ff, d), dt)]
        if model.get("mlp_type") == "swiglu":
            specs.append((p + "mlp/w_gate", (d, ff), dt))
    return sorted(specs)


def nbytes(spec: Spec) -> int:
    _, shape, dt = spec
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dt).itemsize


@functools.partial(jax.jit, static_argnums=(0,))
def _base(specs: Tuple[Spec, ...], key) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape, dt) in enumerate(specs):
        scale = 0.02 if len(shape) == 1 else 1.0 / np.sqrt(shape[0])
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (x * np.float32(scale)).astype(dt)
    return out


def base_weights(specs: List[Spec], seed: int) -> Dict[str, jax.Array]:
    return _base(tuple(specs), root_key(seed))


@functools.partial(jax.jit, static_argnames=("density", "scale", "n_frozen"))
def _finetune(params: Dict[str, jax.Array], key, *, density: float,
              scale: float, n_frozen: int) -> Dict[str, jax.Array]:
    out = {}
    for i, name in enumerate(sorted(params)):
        x = params[name]
        if i < n_frozen:
            out[name] = x
            continue
        k_mask, k_noise = jax.random.split(jax.random.fold_in(key, i))
        mask = jax.random.uniform(k_mask, x.shape) < density
        noise = jax.random.normal(k_noise, x.shape, jnp.float32) * scale
        out[name] = (x.astype(jnp.float32)
                     + jnp.where(mask, noise, 0.0)).astype(x.dtype)
    return out


def finetune(params: Dict[str, jax.Array], seed: int, tag: int, *,
             density: float, scale: float, freeze_frac: float
             ) -> Dict[str, jax.Array]:
    """One derivative of ``params``; ``tag`` tells the draws of one run's
    derivatives apart."""
    n_frozen = int(len(params) * freeze_frac)
    return _finetune(params, jax.random.fold_in(root_key(seed), tag),
                     density=float(density), scale=float(scale),
                     n_frozen=n_frozen)
