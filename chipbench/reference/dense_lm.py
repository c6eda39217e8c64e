"""Plain reference for training a dense decoder LM: forward, loss, gradients
and AdamW, in straightforward ``jax.numpy``.

It imports nothing of the program. It follows the dense block this
repository runs at BERT-base and Yi widths (``chipbench/configs``, under
``assumed``): token embedding times sqrt(d); per layer pre-RMSNorm
(``x * rsqrt(mean(x^2) + 1e-6) * (1 + w)``), rotary positions on half-split
query and key heads, causal softmax attention with grouped key/value heads,
a residual, pre-RMSNorm and a tanh-GELU (or SwiGLU) MLP, a residual; then a
final RMSNorm and the output head. The loss is the mean next-token
cross-entropy. Matrix products run at ``highest`` precision in float32;
a control computes one precision lower (``dtype=bfloat16``, or
``precision="high"``: three bfloat16 passes) and must fail.

Parameters come in the program's stacked layout (a leading layer axis), as
the benchmark hands them over: ``embed/tok``, ``layers/attn/w{q,k,v,o}``,
``layers/mlp/w_{in,out}[,gate]``, ``layers/ln{1,2}``, ``final_norm``,
``lm_head``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6


def _rmsnorm(x, w):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(NORM_EPS, x.dtype)) * (1 + w)


def _rope(x, theta: float):
    """x: (B, S, H, hd); half-split rotation by position."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    c = np.sqrt(2.0 / np.pi)
    return 0.5 * x * (1 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
            dtype=jnp.float32):
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    B, S = tokens.shape
    d, hd = model["d_model"], model["head_dim"]
    H, G = model["n_heads"], model["n_kv_heads"]
    theta = float(model.get("rope_theta", 10000.0))
    x = p["embed"]["tok"][tokens] * jnp.asarray(np.sqrt(d), dtype)
    causal = np.tril(np.ones((S, S), bool))
    for li in range(model["n_layers"]):
        a = {k: v[li] for k, v in p["layers"]["attn"].items()}
        m = {k: v[li] for k, v in p["layers"]["mlp"].items()}
        h = _rmsnorm(x, p["layers"]["ln1"][li])
        q = _rope((h @ a["wq"]).reshape(B, S, H, hd), theta)
        k = _rope((h @ a["wk"]).reshape(B, S, G, hd), theta)
        v = (h @ a["wv"]).reshape(B, S, G, hd)
        k = jnp.repeat(k, H // G, axis=2)
        v = jnp.repeat(v, H // G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(hd ** -0.5,
                                                               dtype)
        s = jnp.where(causal, s, jnp.asarray(-1e30 if dtype == jnp.float32
                                             else -1e4, dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
        x = x + o @ a["wo"]
        h = _rmsnorm(x, p["layers"]["ln2"][li])
        if "w_gate" in m:
            f = jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_in"])
        else:
            f = _gelu_tanh(h @ m["w_in"])
        x = x + f @ m["w_out"]
    x = _rmsnorm(x, p["final_norm"])
    head = p["lm_head"] if "lm_head" in p else p["embed"]["tok"].T
    return x @ head


def loss(params, tokens, model, dtype=jnp.float32):
    logits = forward(params, tokens, model, dtype).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits[:, :-1], axis=-1)
    label = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                axis=-1)[..., 0]
    return jnp.mean(lse - label)


@functools.partial(jax.jit,
                   static_argnames=("model_items", "dtype", "precision"))
def _block_grad(params, tokens, model_items, dtype, precision):
    model = dict(model_items)
    with jax.default_matmul_precision(precision):
        value, grads = jax.value_and_grad(loss)(params, tokens, model, dtype)
    return value, jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                         grads)


def loss_and_grad(params, tokens: np.ndarray, model: Dict[str, Any], *,
                  rows: int, dtype=jnp.float32, precision: str = "highest"):
    """Mean loss and gradient over ``tokens``, computed ``rows`` sequences
    at a time (every block the same size, so the mean of block means is the
    mean)."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if not isinstance(v, (list, dict))))
    rows = min(rows, tokens.shape[0])
    if tokens.shape[0] % rows:
        raise ValueError(f"{tokens.shape[0]} sequences in blocks of {rows}")
    n = tokens.shape[0] // rows
    total, acc = 0.0, None
    for i in range(n):
        value, grads = _block_grad(params, jnp.asarray(
            tokens[i * rows:(i + 1) * rows]), items, dtype, precision)
        total += float(value)
        acc = grads if acc is None else jax.tree_util.tree_map(
            jnp.add, acc, grads)
    return total / n, jax.tree_util.tree_map(lambda g: g / n, acc)


# ---------------------------------------------------------------------------
# AdamW (decoupled weight decay, global-norm clipping, linear warm-up)
# ---------------------------------------------------------------------------

def adamw_step(opt: Dict[str, float], params, grads, mu, nu, t: int
               ) -> Tuple[Any, Any, Any, Any]:
    """Step ``t`` (1-based). Returns (params, mu, nu, clipped grads)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                               for g in leaves)))
    scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    if t < opt["warmup_steps"]:
        lr = opt["lr"] * t / max(opt["warmup_steps"], 1)
    else:
        prog = min(max((t - opt["warmup_steps"]) / max(
            opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
        lr = opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                          * 0.5 * (1 + np.cos(np.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1 - b1 ** t, 1 - b2 ** t

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"]) \
            + opt["weight_decay"] * p
        return p - lr * upd, m, v, g

    out = jax.tree_util.tree_map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def train(params0, batches, model, opt, *, rows: int, dtype=jnp.float32,
          precision: str = "highest", half_batch: bool = False):
    """Follow the first ``len(batches)`` steps from ``params0``. Returns
    ``(losses, clipped first gradient, params after the last step)``.
    ``half_batch`` leaves out the second half of every batch (a fault the
    comparison must catch)."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for t, tokens in enumerate(batches, start=1):
        if half_batch:
            tokens = tokens[:tokens.shape[0] // 2]
        value, grads = loss_and_grad(params, tokens, model, rows=rows,
                                     dtype=dtype, precision=precision)
        params, mu, nu, g = adamw_step(opt, params, grads, mu, nu, t)
        losses.append(value)
        if first is None:
            first = g
    return losses, first, params
