"""Pattern-based decoder stack.

A config declares a repeating ``block_pattern`` (e.g. ``("rec","rec","local")``
for RecurrentGemma).  Layers are grouped as:

    [lead]  first_dense_layers explicit dense blocks (DeepSeek-V2's layer 0)
    [scan]  n_scan_units repetitions of the pattern, parameters stacked with a
            leading unit dim and iterated with ``lax.scan`` (compile time and
            HLO size independent of depth)
    [tail]  the remainder (< pattern length) explicit blocks

Block kinds: dense | local | moe | rwkv | rec.
``block_apply`` returns ``(x, new_cache, aux)``; caches are pytrees stacked
along the unit dim for the scanned segment.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import rwkv6 as W


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg: ArchConfig, kind: str, dtype, dense_override: bool = False):
    """dense_override: build the lead dense layer of an otherwise-moe config."""
    k1, k2 = jax.random.split(key)
    n1, s1 = L.norm_init(cfg.norm_kind, cfg.d_model)
    n2, s2 = L.norm_init(cfg.norm_kind, cfg.d_model)
    p: dict[str, Any] = {"ln1": n1, "ln2": n2}
    s: dict[str, Any] = {"ln1": s1, "ln2": s2}
    if kind in ("dense", "local", "moe"):
        if cfg.attn_kind == "mla":
            p["attn"], s["attn"] = A.mla_init(k1, cfg, dtype)
        else:
            p["attn"], s["attn"] = A.gqa_init(k1, cfg, dtype)
        if kind == "moe" and not dense_override:
            p["moe"], s["moe"] = M.moe_init(k2, cfg, dtype)
        else:
            p["mlp"], s["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    elif kind == "rwkv":
        p["tm"], s["tm"] = W.rwkv_init(k1, cfg, dtype)
        # rwkv_init returns both halves; split storage for clarity
        tm_p, tm_s = p.pop("tm"), s.pop("tm")
        p["core"], s["core"] = tm_p, tm_s
    elif kind == "rec":
        p["rec"], s["rec"] = R.rglru_init(k1, cfg, dtype)
        p["mlp"], s["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        raise ValueError(kind)
    return p, s


def block_apply(
    cfg: ArchConfig,
    kind: str,
    params,
    x,
    *,
    mode: str,
    cache=None,
    pos=None,
    cache_cap: int = 0,
    window_override: Optional[int] = None,
    dense_override: bool = False,
    exact_moe: bool = False,
):
    aux = jnp.zeros((), jnp.float32)
    nk = cfg.norm_kind
    if kind in ("dense", "local", "moe"):
        window = cfg.window if kind == "local" else window_override
        h = L.norm_apply(nk, params["ln1"], x)
        if cfg.attn_kind == "mla":
            a_out, new_cache = A.mla_apply(cfg, params["attn"], h, mode=mode, cache=cache, pos=pos, cache_cap=cache_cap)
        else:
            a_out, new_cache = A.gqa_apply(
                cfg, params["attn"], h, mode=mode, cache=cache, pos=pos, window=window, cache_cap=cache_cap
            )
        x = x + a_out
        h = L.norm_apply(nk, params["ln2"], x)
        if kind == "moe" and not dense_override:
            # decode is always exact (T == B tokens, full capacity is cheap and
            # drops would make cached decoding diverge).  train/prefill default
            # to capacity-factor dispatch (static shapes, Switch/MaxText
            # behaviour; full capacity at 32k-token prefill would materialise
            # (E, T, D) buffers).  ``exact_moe`` opts a whole pipeline into
            # drop-free routing, e.g. for decode-vs-forward consistency checks.
            # fused dispatch is scoped to train/decode: at prefill scale
            # (~1M tokens) the single (T*k) slot table partitions worse under
            # GSPMD and compiled FLOPs/device regress 2.1x (SSPerf H1 iter 3),
            # while train measures -47% FLOPs and decode's tiny T always wins.
            m_out, aux = M.moe_apply(
                cfg, params["moe"], h, cfg.act,
                full_capacity=(mode == "decode") or exact_moe,
                fused=cfg.moe_fused_dispatch and mode != "prefill",
            )
        else:
            m_out = L.mlp_apply(params["mlp"], h, cfg.act)
        x = x + m_out
        return x, new_cache, aux

    if kind == "rwkv":
        cp = params["core"]
        st_tm = None if cache is None else {"tm_last": cache["tm_last"], "s": cache["s"]}
        h = L.norm_apply(nk, params["ln1"], x)
        y, tm_state = W.rwkv_time_mix(cfg, cp, h, mode=mode, state=st_tm)
        x = x + y
        st_cm = None if cache is None else {"cm_last": cache["cm_last"]}
        h = L.norm_apply(nk, params["ln2"], x)
        y, cm_state = W.rwkv_channel_mix(cfg, cp, h, mode=mode, state=st_cm)
        x = x + y
        new_cache = None
        if mode != "train":
            new_cache = {**tm_state, **cm_state}
        return x, new_cache, aux

    if kind == "rec":
        h = L.norm_apply(nk, params["ln1"], x)
        y, new_cache = R.rglru_apply(cfg, params["rec"], h, mode=mode, state=cache)
        x = x + y
        h = L.norm_apply(nk, params["ln2"], x)
        x = x + L.mlp_apply(params["mlp"], h, cfg.act)
        return x, new_cache, aux

    raise ValueError(kind)


def block_cache_shape(cfg: ArchConfig, kind: str, batch: int, cap: int, dtype, window_override=None):
    if kind in ("dense", "moe"):
        if cfg.attn_kind == "mla":
            return A.mla_cache_shape(cfg, batch, cap, dtype)
        return A.gqa_cache_shape(cfg, batch, cap, window_override, dtype)
    if kind == "local":
        return A.gqa_cache_shape(cfg, batch, cap, cfg.window, dtype)
    if kind == "rwkv":
        return W.rwkv_state_shape(cfg, batch, dtype)
    if kind == "rec":
        return R.rglru_state_shape(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# layer grouping
# ---------------------------------------------------------------------------

def layer_plan(cfg: ArchConfig):
    """Returns (n_lead, n_scan_units, tail_kinds)."""
    lead = cfg.first_dense_layers
    rest = cfg.n_layers - lead
    n_units = rest // cfg.pattern_len
    tail = cfg.block_pattern[: rest % cfg.pattern_len]
    return lead, n_units, tail


def stack_init(key, cfg: ArchConfig, dtype):
    lead, n_units, tail = layer_plan(cfg)
    keys = jax.random.split(key, 3)
    p: dict[str, Any] = {}
    s: dict[str, Any] = {}
    if lead:
        lp, ls = [], []
        for i, kk in enumerate(jax.random.split(keys[0], lead)):
            bp, bs = block_init(kk, cfg, cfg.block_pattern[0], dtype, dense_override=True)
            lp.append(bp)
            ls.append(bs)
        p["lead"], s["lead"] = lp, ls  # lists: tuple leaves are reserved for axis specs
    if n_units:
        def unit_init(k):
            up, us = {}, {}
            for bi, kind in enumerate(cfg.block_pattern):
                bp, bs = block_init(jax.random.fold_in(k, bi), cfg, kind, dtype)
                up[f"b{bi}"] = bp
                us[f"b{bi}"] = bs
            return up, us

        unit_keys = jax.random.split(keys[1], n_units)
        stacked = jax.vmap(lambda k: unit_init(k)[0])(unit_keys)
        _, unit_specs = unit_init(unit_keys[0])
        p["units"] = stacked
        # unit params get a leading "unit" axis (never sharded)
        s["units"] = jax.tree.map(lambda sp: ("unit",) + sp, unit_specs, is_leaf=lambda t: isinstance(t, tuple))
    if tail:
        tp, ts = [], []
        for bi, kind in enumerate(tail):
            bp, bs = block_init(jax.random.fold_in(keys[2], bi), cfg, kind, dtype)
            tp.append(bp)
            ts.append(bs)
        p["tail"], s["tail"] = tp, ts
    return p, s


def stack_apply(
    cfg: ArchConfig,
    params,
    x,
    *,
    mode: str,
    cache=None,
    pos=None,
    cache_cap: int = 0,
    window_override: Optional[int] = None,
    exact_moe: bool = False,
):
    """Returns (x, new_cache, aux_sum). ``cache``/new_cache structure:
    {"lead": tuple, "units": stacked pytree, "tail": tuple} (entries omitted
    when that segment is empty)."""
    lead, n_units, tail = layer_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}

    ba = functools.partial(
        block_apply, cfg, mode=mode, pos=pos, cache_cap=cache_cap,
        window_override=window_override, exact_moe=exact_moe,
    )

    if lead:
        caches = []
        for i in range(lead):
            c = None if cache is None else cache["lead"][i]
            x, nc, aux = ba(cfg.block_pattern[0], params["lead"][i], x, cache=c, dense_override=True)
            caches.append(nc)
            aux_total = aux_total + aux
        if mode != "train":
            new_cache["lead"] = caches

    if n_units:
        def unit_fn(x, unit_params, unit_cache):
            ncs = {}
            aux_u = jnp.zeros((), jnp.float32)
            for bi, kind in enumerate(cfg.block_pattern):
                c = None if unit_cache is None else unit_cache[f"b{bi}"]
                x, nc, aux = ba(kind, unit_params[f"b{bi}"], x, cache=c)
                ncs[f"b{bi}"] = nc
                aux_u = aux_u + aux
            return x, ncs, aux_u

        if cfg.remat and mode == "train":
            # keep the outputs of the weight matmuls (dot_generals with no
            # batch dims), recompute the rest in the backward pass
            unit_fn = jax.checkpoint(
                unit_fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        if not cfg.scan_layers:
            # unrolled path: used by the roofline probes (XLA cost analysis
            # counts a scan body once, so depth extrapolation needs unrolling)
            unit_caches = []
            aux_sum = jnp.zeros((), jnp.float32)
            for ui in range(n_units):
                up = jax.tree.map(lambda t: t[ui], params["units"])
                uc = None if cache is None else jax.tree.map(lambda t: t[ui], cache["units"])
                x, nc, aux = unit_fn(x, up, uc)
                unit_caches.append(nc)
                aux_sum = aux_sum + aux
            if mode != "train":
                new_cache["units"] = jax.tree.map(lambda *xs: jnp.stack(xs), *unit_caches)
            aux_total = aux_total + aux_sum
        elif mode == "train":
            def scan_body(c, up):
                y, _, aux = unit_fn(c, up, None)
                return y, aux
            x, auxs = jax.lax.scan(scan_body, x, params["units"])
        elif mode == "prefill":
            def scan_body(c, up):
                y, nc, aux = unit_fn(c, up, None)
                return y, (nc, aux)
            x, (unit_caches, auxs) = jax.lax.scan(scan_body, x, params["units"])
            new_cache["units"] = unit_caches
        else:  # decode
            def scan_body(c, inp):
                up, uc = inp
                y, nc, aux = unit_fn(c, up, uc)
                return y, (nc, aux)
            x, (unit_caches, auxs) = jax.lax.scan(scan_body, x, (params["units"], cache["units"]))
            new_cache["units"] = unit_caches
        if cfg.scan_layers:
            aux_total = aux_total + jnp.sum(auxs)

    if tail:
        caches = []
        for bi, kind in enumerate(tail):
            c = None if cache is None else cache["tail"][bi]
            x, nc, aux = ba(kind, params["tail"][bi], x, cache=c)
            caches.append(nc)
            aux_total = aux_total + aux
        if mode != "train":
            new_cache["tail"] = caches

    return x, (new_cache if mode != "train" else None), aux_total


def stack_cache_shapes(cfg: ArchConfig, batch: int, cap: int, dtype, window_override=None):
    """ShapeDtypeStruct pytree matching stack_apply's cache layout."""
    lead, n_units, tail = layer_plan(cfg)
    out: dict[str, Any] = {}
    bc = lambda kind: block_cache_shape(cfg, kind, batch, cap, dtype, window_override)  # noqa: E731
    if lead:
        out["lead"] = [bc(cfg.block_pattern[0]) for _ in range(lead)]
    if n_units:
        unit = {f"b{bi}": bc(kind) for bi, kind in enumerate(cfg.block_pattern)}
        out["units"] = jax.tree.map(
            lambda sds: jax.ShapeDtypeStruct((n_units,) + sds.shape, sds.dtype), unit
        )
    if tail:
        out["tail"] = [bc(kind) for kind in tail]
    return out


def block_cache_spec(cfg: ArchConfig, kind: str, window_override=None):
    """Logical-axis tuples matching block_cache_shape (for sharding)."""
    if kind in ("dense", "moe"):
        if cfg.attn_kind == "mla":
            return A.mla_cache_spec()
        return A.gqa_cache_spec(window_override)
    if kind == "local":
        return A.gqa_cache_spec(cfg.window)
    if kind == "rwkv":
        return W.rwkv_state_spec()
    if kind == "rec":
        return R.rglru_state_spec()
    raise ValueError(kind)


def stack_cache_specs(cfg: ArchConfig, window_override=None):
    """Logical-axis pytree matching stack_cache_shapes (unit dim prefixed)."""
    lead, n_units, tail = layer_plan(cfg)
    out: dict[str, Any] = {}
    bs = lambda kind: block_cache_spec(cfg, kind, window_override)  # noqa: E731
    if lead:
        out["lead"] = [bs(cfg.block_pattern[0]) for _ in range(lead)]
    if n_units:
        unit = {f"b{bi}": bs(kind) for bi, kind in enumerate(cfg.block_pattern)}
        out["units"] = jax.tree.map(
            lambda sp: ("unit",) + sp, unit, is_leaf=lambda t: isinstance(t, tuple)
        )
    if tail:
        out["tail"] = [bs(kind) for kind in tail]
    return out
