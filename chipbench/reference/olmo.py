"""OLMo forward pass and loss in plain ``jax.numpy`` (arXiv:2402.00838).

Pre-norm decoder blocks with non-parametric LayerNorm (no scale, no bias),
rotary position embeddings (rotate-half form), causal multi-head attention
without biases, a SwiGLU MLP, and the output head tied to the token
embedding.  The loss is the mean next-token cross-entropy.

Every matrix product runs at ``highest`` precision in the dtype of its
inputs (float32 for the reference).  Layers run under ``jax.checkpoint`` so
that one layer's activations are live at a time in the backward pass.

Parameters use the layout of ``param_shapes``: layer weights are stacked
with a leading layer dimension.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(cfg: dict) -> dict:
    """Parameter shapes, nested as the program under test nests them."""
    d, h, hd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    f, v, n = cfg["d_ff"], cfg["vocab_size"], cfg["n_layers"]
    return {
        "embed": {"w": (v, d)},
        "final_norm": {},
        "stack": {"units": {"b0": {
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, h, hd),
                     "wv": (n, d, h, hd), "wo": (n, h, hd, d)},
            "ln1": {}, "ln2": {},
            "mlp": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)},
        }}},
    }


def _layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """Rotate-half rotary embedding of x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, x, w):
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    a, mlp = w["attn"], w["mlp"]
    h = _layer_norm(x, eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wq"], precision=HIGHEST), theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wk"], precision=HIGHEST), theta)
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"], precision=HIGHEST)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    n = x.shape[1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", p, v, precision=HIGHEST)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, a["wo"], precision=HIGHEST)
    h = _layer_norm(x, eps)
    up = jnp.einsum("bsd,df->bsf", h, mlp["wi"], precision=HIGHEST)
    gate = jnp.einsum("bsd,df->bsf", h, mlp["wg"], precision=HIGHEST)
    return x + jnp.einsum("bsf,fd->bsd", up * jax.nn.silu(gate), mlp["wo"],
                          precision=HIGHEST)


def loss(cfg: dict, params, tokens, targets):
    """Mean next-token cross-entropy of tokens (B, S) against targets (B, S)."""
    emb = params["embed"]["w"]
    x = jnp.take(emb, tokens, axis=0)
    layer = jax.checkpoint(lambda x, w: (_block(cfg, x, w), None))
    x, _ = jax.lax.scan(layer, x, params["stack"]["units"]["b0"])
    x = _layer_norm(x, cfg["norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", x, emb, precision=HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def grad(cfg: dict, params, batch):
    """Gradient of ``loss`` over one client's batch {tokens, targets}."""
    return jax.grad(lambda p: loss(cfg, p, batch["tokens"], batch["targets"]))(params)
