"""Top-level language model: embeddings (incl. the multimodal stub frontends),
decoder stack, head(s), loss, and the serve (prefill/decode) paths.

Batch dict:
  tokens   (B, S) int32        -- or (B, K, S) for musicgen's K codebooks
  targets  same shape as tokens (train only)
  patches  (B, P, frontend_dim) -- VLM prefix embeddings (stub frontend)
  loss_mask optional (B, S_pred) f32

``build(cfg)`` returns a ``Model`` with pure functions; params are plain
nested dicts so the federated core can treat them as opaque pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp  # noqa: F401

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import stack as S
from repro.sharding.constraints import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def model_init(key, cfg: ArchConfig):
    dtype = L._dtype(cfg.resolved_state_dtype)
    ks = jax.random.split(key, 5)
    p: dict[str, Any] = {}
    sp: dict[str, Any] = {}

    if cfg.n_codebooks > 1:
        emb = jax.random.normal(ks[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model)) * 0.02
        p["embed"] = {"w": emb.astype(dtype)}
        sp["embed"] = {"w": (None, "vocab", "embed")}
        head = jax.random.normal(ks[1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size)) * 0.02
        p["head"] = {"w": head.astype(dtype)}
        sp["head"] = {"w": (None, "embed", "vocab")}
    else:
        p["embed"], sp["embed"] = L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype)
        for k in p["embed"]:
            p["embed"][k] = p["embed"][k] * 0.02
        if not cfg.tie_embeddings:
            w, s = L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dtype)
            p["head"] = {"w": w}
            sp["head"] = {"w": s}

    if cfg.frontend == "vision":
        w1, s1 = L.dense_init(ks[2], (cfg.frontend_dim, cfg.d_model), (None, "embed"), dtype)
        w2, s2 = L.dense_init(ks[3], (cfg.d_model, cfg.d_model), ("embed", "embed2"), dtype)
        p["projector"] = {"w1": w1, "w2": w2}
        sp["projector"] = {"w1": s1, "w2": s2}

    p["stack"], sp["stack"] = S.stack_init(ks[4], cfg, dtype)
    p["final_norm"], sp["final_norm"] = L.norm_init(cfg.norm_kind, cfg.d_model)
    return p, sp


# ---------------------------------------------------------------------------
# embed / head
# ---------------------------------------------------------------------------

def _embed(cfg: ArchConfig, params, tokens, patches=None):
    # the rows a lookup gathers are cast to the compute dtype, not the table:
    # the lookup's gradient, a scatter-add over every position of a token,
    # then accumulates in the state dtype (in bfloat16 it lost 2 % of OLMo's
    # embedding gradient over 2048 Zipf tokens)
    dtype = L._dtype(cfg.dtype)
    if cfg.n_codebooks > 1:
        # tokens (B, K, S): summed codebook embeddings
        x = 0.0
        for kb in range(cfg.n_codebooks):
            x = x + jnp.take(params["embed"]["w"][kb], tokens[:, kb], axis=0).astype(dtype)
        return x
    x = L.embed_apply(params["embed"], tokens).astype(dtype)
    if cfg.frontend == "vision" and patches is not None:
        pj = params["projector"]
        pre = jax.nn.gelu(patches.astype(x.dtype) @ pj["w1"]) @ pj["w2"]
        x = jnp.concatenate([pre, x], axis=1)
    return x


def _head(cfg: ArchConfig, params, x):
    if cfg.n_codebooks > 1:
        logits = jnp.einsum("bsd,kdv->bskv", x, params["head"]["w"]).astype(jnp.float32)
        return constrain(logits, *([None] * (logits.ndim - 1)), "model")
    if cfg.tie_embeddings:
        logits = L.head_apply(params["embed"]["w"], x)
    else:
        logits = (x @ params["head"]["w"]).astype(jnp.float32)
    # keep the vocab dim sharded: unsharded logits are the single largest
    # activation in LM training (B*S*V*4 bytes)
    return constrain(logits, *([None] * (logits.ndim - 1)), "model")


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def compute_params(cfg: ArchConfig, params):
    """The stored weights cast to the compute dtype, where the two differ
    (``cfg.state_dtype``).  The forward pass does this once, at entry, so
    ``jax.grad`` transposes the cast and returns the gradient in the state
    dtype.  The embedding's lookups read the stored table (``_embed``)."""
    state, compute = L._dtype(cfg.resolved_state_dtype), L._dtype(cfg.dtype)
    if state == compute:
        return params
    with jax.named_scope("model.param_cast"):
        return jax.tree.map(lambda a: a.astype(compute) if a.dtype == state else a,
                            params)


def forward(cfg: ArchConfig, params, batch, *, mode="train", cache=None, pos=None,
            cache_cap: int = 0, window_override: Optional[int] = None,
            exact_moe: bool = False):
    stored, params = params, compute_params(cfg, params)
    x = _embed(cfg, dict(params, embed=stored["embed"]), batch["tokens"],
               batch.get("patches"))
    x, new_cache, aux = S.stack_apply(
        cfg, params["stack"], x, mode=mode, cache=cache, pos=pos,
        cache_cap=cache_cap, window_override=window_override, exact_moe=exact_moe,
    )
    x = L.norm_apply(cfg.norm_kind, params["final_norm"], x)
    logits = _head(cfg, params, x)
    return logits, new_cache, aux


def _xent(logits, targets, mask=None):
    lse = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction instead of take_along_axis: a gather over the
    # vocab-sharded dim would force GSPMD to all-gather the full logits
    # (observed +13 GiB/device on olmo-1b); the one-hot einsum contracts
    # shard-locally and psums a scalar.
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    onehot = constrain(onehot, *([None] * (onehot.ndim - 1)), "model")
    tgt = jnp.einsum("...v,...v->...", logits, onehot)
    nll = lse - tgt
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def loss_fn(cfg: ArchConfig, params, batch):
    """Causal LM loss; returns (loss, aux_dict)."""
    logits, _, aux = forward(cfg, params, batch, mode="train")
    if cfg.n_codebooks > 1:
        # logits (B,S,K,V) vs targets (B,K,S)
        tgt = jnp.moveaxis(batch["targets"], 1, 2)  # (B,S,K)
        loss = _xent(logits, tgt, batch.get("loss_mask"))
    elif cfg.frontend == "vision":
        n_text = batch["tokens"].shape[1]
        text_logits = logits[:, -n_text:]
        loss = _xent(text_logits, batch["targets"], batch.get("loss_mask"))
    else:
        loss = _xent(logits, batch["targets"], batch.get("loss_mask"))
    total = loss + 0.01 * aux
    return total, {"xent": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(cfg: ArchConfig, params, batch, *, cache_cap: int,
            window_override: Optional[int] = None, exact_moe: bool = False):
    """Returns (last_token_logits, cache). cache carries a scalar "pos"."""
    logits, new_cache, _ = forward(
        cfg, params, batch, mode="prefill", cache_cap=cache_cap,
        window_override=window_override, exact_moe=exact_moe,
    )
    seq = batch["tokens"].shape[-1]
    if cfg.frontend == "vision" and batch.get("patches") is not None:
        seq = seq + batch["patches"].shape[1]
    cache = {"layers": new_cache, "pos": jnp.asarray(seq, jnp.int32)}
    return logits[:, -1], cache


def decode_step(cfg: ArchConfig, params, cache, tokens, *,
                window_override: Optional[int] = None):
    """tokens: (B, 1) int32 (or (B, K, 1) musicgen). Returns (logits, cache)."""
    pos = cache["pos"]
    batch = {"tokens": tokens}
    logits, new_layers, _ = forward(
        cfg, params, batch, mode="decode", cache=cache["layers"], pos=pos,
        window_override=window_override,
    )
    return logits[:, -1] if cfg.n_codebooks == 1 else logits[:, -1], {
        "layers": new_layers,
        "pos": pos + 1,
    }


def cache_shapes(cfg: ArchConfig, batch: int, cap: int, *,
                 window_override: Optional[int] = None):
    dtype = L._dtype(cfg.dtype)
    layers = S.stack_cache_shapes(cfg, batch, cap, dtype, window_override)
    return {"layers": layers, "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def cache_specs(cfg: ArchConfig, *, window_override: Optional[int] = None):
    """Logical-axis pytree parallel to cache_shapes (sharding rules input)."""
    return {"layers": S.stack_cache_specs(cfg, window_override), "pos": ()}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable  # (key) -> params
    specs: Callable  # () -> logical-axis pytree (same structure as params)
    loss: Callable  # (params, batch) -> (loss, aux)
    apply: Callable  # (params, batch) -> logits
    prefill: Callable  # (params, batch, cache_cap) -> (logits, cache)
    decode: Callable  # (params, cache, tokens) -> (logits, cache)
    cache_shapes: Callable  # (batch, cap) -> ShapeDtypeStruct pytree
    cache_specs: Callable  # () -> logical-axis pytree (parallel to cache_shapes)


def build(cfg: ArchConfig, *, window_override: Optional[int] = None) -> Model:
    _specs_cache: list = []

    def init(key):
        p, sp = model_init(key, cfg)
        if not _specs_cache:
            _specs_cache.append(sp)
        return p

    def specs():
        if not _specs_cache:
            box = {}

            def f(key):
                p, sp = model_init(key, cfg)
                box["sp"] = sp  # static metadata captured during trace
                return p

            jax.eval_shape(f, jax.random.key(0))
            _specs_cache.append(box["sp"])
        return _specs_cache[0]

    return Model(
        cfg=cfg,
        init=init,
        specs=specs,
        loss=lambda p, b: loss_fn(cfg, p, b),
        apply=lambda p, b: forward(cfg, p, b, mode="train", window_override=window_override)[0],
        prefill=lambda p, b, cap, **kw: prefill(cfg, p, b, cache_cap=cap, window_override=window_override, **kw),
        decode=lambda p, c, t: decode_step(cfg, p, c, t, window_override=window_override),
        cache_shapes=lambda b, cap: cache_shapes(cfg, b, cap, window_override=window_override),
        cache_specs=lambda: cache_specs(cfg, window_override=window_override),
    )
