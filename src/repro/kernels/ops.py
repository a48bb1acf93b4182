"""Jit-ready kernel wrappers with implementation dispatch.

``impl`` selects the backend:
  * ``"xla"``              -- chunked pure-jnp path
  * ``"pallas"``           -- Pallas TPU kernel (the deployment target)
  * ``"pallas_interpret"`` -- Pallas kernel body interpreted on CPU; used by
                              the kernel test-suite to validate the TPU code.

``impl=None`` (every library call site) resolves from the platform
(``default_impl``): ``"pallas"`` when JAX's default backend is a TPU,
``"xla"`` elsewhere.  Flash attention and wkv6 are the exception: they have
no Pallas backward, so they resolve to ``"xla"`` everywhere (see
``flash_attention``).  ``RESOLVED`` records what each op resolved to at its
last trace, so a run can report the path it took.

Under ``jax.set_mesh`` with client axes that split the client dim, the
per-client-row kernels run on each device's own rows
(``sharding.constraints.per_client``): the TPU compiler cannot partition a
Pallas kernel itself.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.sharding.constraints import per_client


def _step_arr(step):
    """None for a host scalar step (the baked-constant kernel path, bitwise
    unchanged from before per-client stepsizes existed); a (m,) f32 array for
    the per-client auto-eta path (``core.autotune``), fed to the kernels as a
    per-client stepsize OPERAND instead of a baked constant."""
    if np.ndim(step) == 0:
        return None
    return jnp.asarray(step, jnp.float32)


# op name -> implementation it resolved to when last traced
RESOLVED: dict[str, str] = {}
# elementwise arena kernel -> the (bm, bw) blocks its Pallas call read the
# (m, width) arena in at its last trace (``round_tail._flat_blocks``)
LAYOUT: dict[str, tuple] = {}


def _scoped(fn):
    """Run an arena op under a name scope of its own name, its ``RESOLVED``
    key: the kernel and its operand plumbing then carry the op's name in
    the compiled HLO's ``op_name`` and in a profiler trace's ``tf_op``."""

    @functools.wraps(fn)
    def op(*args, **kwargs):
        with jax.named_scope(fn.__name__):
            return fn(*args, **kwargs)

    return op


def default_impl() -> str:
    """The platform's implementation: Pallas kernels on a TPU, XLA elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str], op: str) -> str:
    impl = impl or default_impl()
    assert impl in ("xla", "pallas", "pallas_interpret"), impl
    RESOLVED[op] = impl
    return impl


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _flash_xla(q, k, v, q_pos, k_pos, *, causal, window, q_chunk, k_chunk, causal_skip):
    """Chunked online-softmax attention (memory O(q_chunk * k_chunk)).

    Outer python loop over q chunks (so ``causal_skip`` can shrink the k range
    statically per chunk -- that halves causal FLOPs); inner ``lax.scan`` over
    k chunks carrying the online-softmax accumulators.
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(hd)

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0, (Sq, q_chunk, Sk, k_chunk)
    nq = Sq // q_chunk

    q5 = q.reshape(B, Sq, Hkv, G, hd)
    out_chunks = []
    for i in range(nq):
        qi = jax.lax.dynamic_slice_in_dim(q5, i * q_chunk, q_chunk, axis=1)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, i * q_chunk, q_chunk, axis=0)
        lo, hi = 0, Sk
        if causal_skip and causal:
            # static bounds: this q chunk covers absolute q positions
            # [i*q_chunk, (i+1)*q_chunk) when q_pos is an arange (train or
            # full prefill); key positions beyond hi are always masked.
            hi = min(Sk, _ceil_to((i + 1) * q_chunk, k_chunk))
            if window is not None:
                lo = max(0, ((i * q_chunk - window) // k_chunk) * k_chunk)
        nk = (hi - lo) // k_chunk
        ks = jax.lax.dynamic_slice_in_dim(k, lo, hi - lo, axis=1).reshape(B, nk, k_chunk, Hkv, hd)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, hi - lo, axis=1).reshape(B, nk, k_chunk, Hkv, vd)
        kps = jax.lax.dynamic_slice_in_dim(k_pos, lo, hi - lo, axis=0).reshape(nk, k_chunk)

        def kv_step(carry, inp, qi=qi, qpi=qpi):
            m, l, acc = carry
            kj, vj, kpj = inp  # (B,kc,Hkv,hd), (B,kc,Hkv,vd), (kc,)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), kj.astype(jnp.float32)
            ) * scale
            valid = kpj[None, :] >= 0
            if causal:
                valid = valid & (kpj[None, :] <= qpi[:, None])
            if window is not None:
                valid = valid & (kpj[None, :] > qpi[:, None] - window)
            s = jnp.where(valid[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhv->bhgqv", p, vj.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, vd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0), kps),
        )
        o = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,Hkv,G,qc,vd)
        out_chunks.append(jnp.moveaxis(o, 3, 1).reshape(B, q_chunk, H, vd))
    return jnp.concatenate(out_chunks, axis=1).astype(q.dtype)


def flash_attention(
    q,
    k,
    v,
    q_pos,
    k_pos,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    causal_skip: bool = True,
    impl: Optional[str] = None,
):
    """Causal (optionally sliding-window) GQA attention.

    q (B,Sq,H,hd); k (B,Sk,Hkv,hd); v (B,Sk,Hkv,vd); positions as in
    ``ref.attention_ref``.
    """
    # No Pallas backward exists, and the model calls this under jax.grad in
    # every training step, so the default is "xla" on every platform;
    # impl="pallas" runs the forward-only kernel (serving, compile tests).
    impl = _resolve(impl or "xla", "flash_attention")
    if impl == "xla":
        return _flash_xla(
            q, k, v, q_pos, k_pos,
            causal=causal, window=window,
            q_chunk=q_chunk, k_chunk=k_chunk, causal_skip=causal_skip,
        )
    from repro.kernels import flash_attention as fa

    return fa.flash_attention_pallas(
        q, k, v, q_pos, k_pos,
        causal=causal, window=window,
        interpret=(impl == "pallas_interpret"),
    )


def attend_cache(q, k_cache, v_cache, q_pos, k_pos, *, window: Optional[int] = None):
    """Single-token decode attention against a (possibly ring-buffer) cache.

    q: (B, 1, H, hd); caches (B, S, Hkv, hd/vd); q_pos scalar int; k_pos (S,).
    """
    B, _, H, hd = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32)) / math.sqrt(hd)
    valid = (k_pos >= 0) & (k_pos <= q_pos)
    if window is not None:
        valid = valid & (k_pos > q_pos - window)
    s = jnp.where(valid[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhv->bhgv", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# rwkv6 chunked wkv
# ---------------------------------------------------------------------------

def _wkv6_chunked_xla(r, k, v, w, u, s0, *, chunk: int):
    """Chunked-parallel WKV6: O(S/C * C^2) intra-chunk matmuls + O(S/C) state
    updates, mathematically identical to the sequential recurrence.

    Let la_t = sum_{tau<=t} log w_tau (within chunk; la_0 = 0 at chunk start).
      y_t   = (r_t * exp(la_{t-1})) @ S_0
            + sum_{tau<t} [(r_t * exp(la_{t-1} - la_tau)) . k_tau] v_tau
            + (r_t . u . k_t) v_t
      S_C   = diag(exp(la_C)) S_0 + sum_tau diag(exp(la_C - la_tau)) k_tau v_tau^T
    """
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n = S // chunk

    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))  # (B,S,H,K)
    uf = u.astype(jnp.float32)

    def chunk_step(s, inp):
        rc, kc, vc, lwc = inp  # (B,C,H,K) etc.
        la = jnp.cumsum(lwc, axis=1)  # (B,C,H,K), inclusive
        la_prev = la - lwc  # exclusive cumsum: sum_{tau < t}
        # inter-chunk: contribution of carried state (la_prev <= 0, exp safe)
        r_dec = rc * jnp.exp(la_prev)
        y_inter = jnp.einsum("bchk,bhkv->bchv", r_dec, s)
        # intra-chunk: pairwise decay exp(la_prev[t] - la[tau]) for tau < t.
        # Computed as a clamped pairwise difference -- the two factors
        # exp(la_prev) * exp(-la) can individually overflow even though the
        # product is <= 1 for tau < t.
        diff = la_prev[:, :, None] - la[:, None, :]  # (B, t, tau, H, K)
        dec = jnp.exp(jnp.minimum(diff, 0.0))
        att = jnp.einsum("bthk,bchk,btchk->bhtc", rc, kc, dec)
        t_idx = jnp.arange(chunk)
        mask = t_idx[:, None] > t_idx[None, :]
        att = jnp.where(mask[None, None], att, 0.0)
        bonus = jnp.einsum("bthk,bthk->bth", rc * uf[None, None], kc)
        y = y_inter + jnp.einsum("bhtc,bchv->bthv", att, vc) + bonus[..., None] * vc
        # state update
        la_end = la[:, -1:]  # (B,1,H,K)
        dec_k = kc * jnp.exp(la_end - la)  # decay from tau to chunk end
        s_new = jnp.exp(la_end[:, 0])[..., None] * s + jnp.einsum("bchk,bchv->bhkv", dec_k, vc)
        return s_new, y

    resh = lambda a: jnp.moveaxis(a.reshape(B, n, chunk, H, -1), 1, 0)
    s_final, ys = jax.lax.scan(
        chunk_step, s0.astype(jnp.float32), (resh(rf), resh(kf), resh(vf), resh(lw))
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, V)
    return y.astype(r.dtype), s_final


def wkv6(r, k, v, w, u, s0, *, chunk: int = 64, impl: Optional[str] = None):
    """RWKV-6 recurrence. Shapes as in ``ref.wkv6_ref``.  Like
    ``flash_attention``: no Pallas backward, so "xla" unless asked."""
    impl = _resolve(impl or "xla", "wkv6")
    if impl == "xla":
        return _wkv6_chunked_xla(r, k, v, w, u, s0, chunk=chunk)
    from repro.kernels import wkv6 as wk

    return wk.wkv6_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=(impl == "pallas_interpret"))


def wkv6_step(r1, k1, v1, w1, u, s):
    """Single decode step. r1,k1,w1: (B,H,K); v1: (B,H,V); s: (B,H,K,V)."""
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r1, k1, v1, w1))
    sf = s.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", rf, sf + u.astype(jnp.float32)[None, :, :, None] * kv)
    s_new = wf[..., :, None] * sf + kv
    return y.astype(r1.dtype), s_new


# ---------------------------------------------------------------------------
# fused federated client update
# ---------------------------------------------------------------------------

def fused_update(x, g, xs, lam, step, rho, *, impl: Optional[str] = None,
                 block: Optional[int] = None):
    """Fused federated inner step (paper eq. (20)); see ``ref.fused_update_ref``.

    The Pallas kernel fuses 4 HBM reads + 1 write into one pass -- the client
    inner loop is memory-bound, so unfused XLA would read/write 6 arrays.
    ``block=None`` resolves to the single module-wide default
    (``fused_update.BLOCK_ROWS``), checked against the VMEM budget.

    ``step`` is a scalar or a per-client array already broadcastable against
    ``x`` (the pytree tmap path reshapes a (m,) stepsize to (m, 1, ..) per
    leaf); the array form rides the pure-jnp reference -- the per-leaf pytree
    layout is not the per-client-eta deployment path, the arena is.
    """
    impl = _resolve(impl, "fused_update")
    if impl == "xla" or _step_arr(step) is not None:
        return _ref.fused_update_ref(x, g, xs, lam, step, rho)
    from repro.kernels import fused_update as fu

    return fu.fused_update_pallas(
        x, g, xs, lam, step, rho, block=block or fu.BLOCK_ROWS,
        interpret=(impl == "pallas_interpret"),
    )


# ---------------------------------------------------------------------------
# fused round tail over the flat client-state arena (core.arena layout:
# (m, width) client buffers, (width,) server rows, width % 128 == 0)
# ---------------------------------------------------------------------------

@_scoped
def fused_update_arena(x, g, x_s, lam, step, rho, *, impl: Optional[str] = None,
                       block: Optional[int] = None):
    """Eq. (20) inner step over the whole packed arena: x, g (m, width);
    lam (m, width) or None (dual term dropped -- SCAFFOLD/FedAvg's rho = 0
    plain steps); x_s (width,) server row broadcast in-kernel (never
    materialised in HBM).  ONE kernel launch per inner step instead of one
    per pytree leaf.

    ``step``: scalar (baked into the kernel -- bitwise the pre-auto-eta
    graph) or (m,) per-client stepsizes (``core.autotune``), fed to the
    kernel as a broadcast row operand."""
    impl = _resolve(impl, "fused_update_arena")
    step_a = _step_arr(step)
    if impl == "xla":
        return _update_arena_xla(x, g, x_s, lam, step, step_a, rho)
    from repro.kernels import round_tail as rt

    return per_client(
        lambda x, g, lam, step_a, x_s: rt.fused_update_arena_pallas(
            x, g, x_s, lam, step if step_a is None else step_a, rho,
            block=block, interpret=(impl == "pallas_interpret")),
        (x, g, lam, step_a), (x_s,))


def _update_arena_xla(x, g, x_s, lam, step, step_a, rho):
    step_b = step if step_a is None else step_a[:, None]
    return _ref.fused_update_ref(
        x, g, x_s[None] if x_s.ndim == 1 else x_s, lam, step_b, rho)


def fused_update_arena_sum(x, g, x_s, lam, xsum, step, rho, *,
                           impl: Optional[str] = None,
                           block: Optional[int] = None):
    """Eq. (20) over the arena, as ``fused_update_arena`` with the dual, and
    the running sum of the inner iterates in the same pass: returns (x',
    xsum + x'), the sum in the state dtype.  ``xsum=None`` is the first
    step, whose sum is x' itself: it reads no zeros buffer, and runs under
    the plain kernel's name with its operands.  Later steps run under
    ``fused_update_arena_sum`` and write the sum in place."""
    name = "fused_update_arena" if xsum is None else "fused_update_arena_sum"
    with jax.named_scope(name):
        impl = _resolve(impl, name)
        step_a = _step_arr(step)
        if impl == "xla":
            new = _update_arena_xla(x, g, x_s, lam, step, step_a, rho)
            return new, new if xsum is None else xsum + new
        from repro.kernels import round_tail as rt

        return per_client(
            lambda x, g, lam, xsum, step_a, x_s: rt.fused_update_arena_sum_pallas(
                x, g, x_s, lam, xsum, step if step_a is None else step_a, rho,
                block=block, interpret=(impl == "pallas_interpret")),
            (x, g, lam, xsum, step_a), (x_s,))


@_scoped
def fused_update_client(x, g, x_s, lam, i, step, rho, *,
                        impl: Optional[str] = None):
    """Eq. (20) on row ``i`` of the arena alone: x, lam (m, width); g
    (width,) client i's gradient; x_s (width,) the server row; ``i`` a
    traced client index.  ``step``: scalar, or (m,) per-client stepsizes
    of which row i's is taken.  Returns x with row i stepped and the other
    rows as they were, written in place.  The caller runs it on its own
    client rows (``core.api.step_by_client`` does so under a client-sharded
    mesh)."""
    impl = _resolve(impl, "fused_update_client")
    step_a = _step_arr(step)
    if impl == "xla":
        row = lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)  # noqa: E731
        new = _ref.fused_update_ref(row(x), g, x_s, row(lam),
                                    step if step_a is None else row(step_a), rho)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, 0)
    from repro.kernels import round_tail as rt

    return rt.fused_update_client_pallas(
        x, g, x_s, lam, i, step if step_a is None else step_a, rho,
        interpret=(impl == "pallas_interpret"))


@_scoped
def inner_loop_affine(x0, H, c, x_s, lam, step, rho, K: int, *,
                      off=None, impl: Optional[str] = None):
    """The WHOLE K-step eq. (20) inner loop for affine gradient oracles
    (grad_i(x) = H_i x - (c_i + off_i) in arena coordinates): one kernel
    keeps each client's row block + H in VMEM across all K steps -- 1 HBM
    read + 1 write of the client state for the whole loop instead of K round
    trips.

    x0, c: (m, W); H: (m, W, W); x_s: (W,).  ``lam=None`` drops the dual
    operand (SCAFFOLD/FedAvg run rho = 0 with no dual); ``off`` is the
    optional per-client offset row added to the affine constant -- the
    SCAFFOLD control-variate buffer rides here with zero extra HBM
    materialisation.  Returns (x_K, x_bar).  Callers must gate on
    ``affine_inner_fits(W)`` (the VMEM budget).

    ``step``: scalar (baked -- bitwise the pre-auto-eta kernel) or (m,)
    per-client stepsizes fed as a row operand (``core.autotune``).
    """
    impl = _resolve(impl, "inner_loop_affine")
    step_a = _step_arr(step)
    if impl == "xla":
        f32 = jnp.float32
        step_b = step if step_a is None else step_a[:, None]
        x_s_b = x_s.astype(f32)[None]
        lam_f = lam.astype(f32) if lam is not None else None
        Hf, cf = H.astype(f32), c.astype(f32)
        if off is not None:
            cf = cf + off.astype(f32)

        def body(carry, _):
            x, xsum = carry
            g = jnp.einsum("mij,mj->mi", Hf, x) - cf
            acc = g + rho * (x - x_s_b)
            if lam_f is not None:
                acc = acc + lam_f
            x = x - step_b * acc
            return (x, xsum + x), None

        init = (x0.astype(f32), jnp.zeros_like(x0, f32))
        (x_K, xsum), _ = jax.lax.scan(body, init, None, length=K)
        return x_K.astype(x0.dtype), (xsum * (1.0 / K)).astype(x0.dtype)
    from repro.kernels import inner_loop as il

    return per_client(
        lambda x0, H, c, lam, off, step_a, x_s: il.inner_loop_affine_pallas(
            x0, H, c, x_s, lam, step if step_a is None else step_a, rho, K,
            off=off, interpret=(impl == "pallas_interpret")),
        (x0, H, c, lam, off, step_a), (x_s,))


@_scoped
def scaffold_cv(c_i, x_K, c_s, x_s, alpha, *, impl: Optional[str] = None,
                block: Optional[int] = None):
    """SCAFFOLD eq. (30) control-variate refresh, fused into one pass:

        c_i' = c_i - c + alpha (x_s - x_K)          (alpha = 1/(K eta))

    c_i, x_K: (m, width) client buffers; c_s, x_s: (width,) server rows
    broadcast in-kernel.  2 client reads + 1 write instead of the ~5-pass
    per-leaf tmap chain (which additionally materialises both server
    broadcasts at (m, width)).

    ``alpha``: scalar (baked) or (m,) per-client 1/(K eta_i) under auto-eta
    (``core.autotune``), fed as a row operand."""
    impl = _resolve(impl, "scaffold_cv")
    alpha_a = _step_arr(alpha)
    if impl == "xla":
        f32 = jnp.float32
        alpha_b = alpha if alpha_a is None else alpha_a[:, None]
        out = (c_i.astype(f32) - c_s.astype(f32)[None]
               + alpha_b * (x_s.astype(f32)[None] - x_K.astype(f32)))
        return out.astype(c_i.dtype)
    from repro.kernels import round_tail as rt

    return per_client(
        lambda c_i, x_K, alpha_a, c_s, x_s: rt.scaffold_cv_pallas(
            c_i, x_K, c_s, x_s, alpha if alpha_a is None else alpha_a,
            block=block, interpret=(impl == "pallas_interpret")),
        (c_i, x_K, alpha_a), (c_s, x_s))


def affine_inner_fits(width: int) -> bool:
    """Static VMEM gate for ``inner_loop_affine`` (see ``inner_loop.vmem_bytes``)."""
    from repro.kernels import inner_loop as il

    return il.fits_vmem(width)


@_scoped
def round_tail(x_ref, lam_s, x_s, rho, *, with_lam_is: bool = True,
               scale: Optional[float] = None, impl: Optional[str] = None,
               block: Optional[int] = None):
    """Fused dual flip + uplink (eqs. 23/24 + Alg. 1 line 8):

        lam_is = rho (x_s - x_ref) - lam_s
        uplink = x_ref - lam_is / rho

    3 HBM reads + 2 writes in one pass instead of ~4 separate passes.
    x_ref, lam_s: (m, width); x_s: (width,).  Returns (lam_is, uplink);
    ``with_lam_is=False`` (the non-trace training path -- callers discard
    lam_is) skips the lam_is output: 3 reads + 1 write, returns (None, u).

    ``scale``: None reads ``x_ref`` as it is.  A static factor (1/K) makes
    the first operand the running sum of the K inner iterates: the kernel
    forms x_ref = sum * scale in the state dtype, the rounding of an XLA
    ``sum * scale``, so the average is never a pass of its own."""
    impl = _resolve(impl, "round_tail")
    if impl == "xla":
        xr = (x_ref if scale is None else x_ref * scale).astype(jnp.float32)
        lam = lam_s.astype(jnp.float32)
        xs = x_s.astype(jnp.float32)[None]
        lam_is = rho * (xs - xr) - lam
        uplink = (xr - lam_is / rho).astype(x_ref.dtype)
        return (lam_is.astype(x_ref.dtype) if with_lam_is else None), uplink
    from repro.kernels import round_tail as rt

    return per_client(
        lambda x_ref, lam_s, x_s: rt.round_tail_pallas(
            x_ref, lam_s, x_s, rho, with_lam_is=with_lam_is, scale=scale,
            block=block, interpret=(impl == "pallas_interpret")),
        (x_ref, lam_s), (x_s,))


@_scoped
def dual_from_uplink(uplink, x_s, rho, *, impl: Optional[str] = None,
                     block: Optional[int] = None):
    """lam_s' = rho (u - x_s') -- the post-all-reduce dual refresh; one pass."""
    impl = _resolve(impl, "dual_from_uplink")
    if impl == "xla":
        out = rho * (uplink.astype(jnp.float32) - x_s.astype(jnp.float32)[None])
        return out.astype(uplink.dtype)
    from repro.kernels import round_tail as rt

    return per_client(
        lambda uplink, x_s: rt.dual_from_uplink_pallas(
            uplink, x_s, rho, block=block,
            interpret=(impl == "pallas_interpret")),
        (uplink,), (x_s,))


@_scoped
def screen_uplink(u, ref, *, impl: Optional[str] = None,
                  block: Optional[int] = None):
    """Fused uplink screening (robustness layer): per-client finite flags
    and squared deviations in ONE pass over the (m, width) uplink buffer.

        finite_i = every entry of u_i is finite
        sq_i     = sum over the FINITE entries of (u_i - ref)^2

    The deviation excludes non-finite entries (the flag already demotes
    those rows), so sq is always finite and comparable across backends.
    ``ref``: (width,) broadcast downlink row -- deviation from x_s catches
    sign flips, which a plain norm cannot -- or (m, width) per-row
    reference (graph rounds screen each node against its own carry).
    Returns ``(finite (m,) bool, sq (m,) f32)``.
    """
    impl = _resolve(impl, "screen_uplink")
    if impl == "xla":
        uf = u.astype(jnp.float32)
        rf = ref.astype(jnp.float32)
        if rf.ndim == 1:
            rf = rf[None]
        fin_e = jnp.isfinite(uf)
        d = jnp.where(fin_e, uf - rf, 0.0)
        return jnp.all(fin_e, axis=1), jnp.sum(d * d, axis=1)
    from repro.kernels import screen as sk

    per_row = ref.ndim == 2
    return per_client(
        lambda u, ref_r, *ref_s: sk.screen_uplink_pallas(
            u, ref_r if per_row else ref_s[0], block=block,
            interpret=(impl == "pallas_interpret")),
        (u, ref if per_row else None), () if per_row else (ref,))


@_scoped
def residual_norm(x, x_prev, *, impl: Optional[str] = None,
                  block: Optional[int] = None):
    """Fused fixed-point residual norms (the early-termination criterion,
    ``core.autotune``): ONE pass over the (m, width) client-state arena and
    its previous-round snapshot emitting, per client row,

        dx2_i = ||x_i - x_prev_i||^2        (fixed-point residual)
        x2_i  = ||x_i||^2                   (normaliser)

    so the driver can evaluate pfb-clean's relative stopping rule
    ``||x - x_prev|| / ||x|| < tol`` without a second read of either buffer.
    Returns ``(dx2 (m,) f32, x2 (m,) f32)``; all math in f32.
    """
    impl = _resolve(impl, "residual_norm")
    if impl == "xla":
        xf = x.astype(jnp.float32)
        d = xf - x_prev.astype(jnp.float32)
        return jnp.sum(d * d, axis=1), jnp.sum(xf * xf, axis=1)
    from repro.kernels import residual as rs

    return per_client(
        lambda x, x_prev: rs.residual_norm_pallas(
            x, x_prev, block=block, interpret=(impl == "pallas_interpret")),
        (x, x_prev))


@_scoped
def stale_mix(uplink, cache, buf, fresh, store, w, *, impl: Optional[str] = None,
              block: Optional[int] = None):
    """Fused stale-uplink admission mix (bounded-staleness engine, ISSUE 7):
    ONE pass over the uplink + stale-buffer arenas emitting the round's
    mixed contribution rows and the updated stale buffer.

        base_i  = uplink_i if fresh_i else cache_i      (today's masked select)
        mixed_i = base_i + w_i (buf_i - base_i) if w_i > 0 else base_i
        buf'_i  = uplink_i if store_i else buf_i

    ``cache``: (width,) broadcast server row or (m, width) per-client cache.
    The ``w_i > 0`` guard keeps the w = 0 rows BITWISE equal to the plain
    select (no -0.0 flips, no 0 * non-finite NaNs), which is what collapses
    ``max_staleness=0`` to the synchronous masked round exactly.  The mix
    arithmetic runs in f32 and casts back, matching the pallas kernel.
    Returns ``(mixed, buf_new)``.
    """
    impl = _resolve(impl, "stale_mix")
    if impl == "xla":
        cache2 = cache if cache.ndim == 2 else cache[None]
        base = jnp.where(fresh[:, None], uplink, cache2)
        bf = base.astype(jnp.float32)
        mixf = bf + w[:, None].astype(jnp.float32) * (buf.astype(jnp.float32) - bf)
        mixed = jnp.where((w > 0)[:, None], mixf.astype(base.dtype), base)
        buf_new = jnp.where(store[:, None], uplink, buf)
        return mixed, buf_new
    from repro.kernels import stale_mix as sm

    per_row = cache.ndim == 2
    return per_client(
        lambda uplink, cache_r, buf, fresh, store, w, *cache_s:
            sm.stale_mix_pallas(
                uplink, cache_r if per_row else cache_s[0], buf, fresh,
                store, w, block=block, interpret=(impl == "pallas_interpret")),
        (uplink, cache if per_row else None, buf, fresh, store, w),
        () if per_row else (cache,))


def _ef21_row_scales(rowmax, leaf_rows, lo: float):
    """Expand per-(client, leaf) maxima to per-128-lane-row scales.  The
    arena pads each leaf to a 128-lane multiple, so leaf boundaries fall on
    row edges and this is a static segment reduction -- same per-(client,
    leaf) scale semantics as ``tree_util._qdq``."""
    m = rowmax.shape[0]
    parts = []
    r0 = 0
    for rk in leaf_rows:
        s = jnp.max(rowmax[:, r0:r0 + rk], axis=1, keepdims=True) / lo
        parts.append(jnp.broadcast_to(s, (m, rk)))
        r0 += rk
    assert r0 == rowmax.shape[1], (r0, rowmax.shape)
    scales = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return jnp.maximum(scales, 1e-12)


@_scoped
def ef21_update(u, u_hat, bits: int, leaf_rows, *, impl: Optional[str] = None,
                block: Optional[int] = None):
    """Fused EF21 quantise-delta over the arena: returns the integrated
    server view u_hat' = u_hat + qdq(u - u_hat) in TWO full-size passes
    (rowwise max-abs reduction + apply) instead of the per-leaf
    tree_sub -> _qdq -> tree_add chain (~4 passes).

    ``leaf_rows``: static per-leaf row counts (``ArenaSpec.leaf_rows()``);
    the quantisation scale is per (client, leaf), exactly as the pytree path.
    """
    impl = _resolve(impl, "ef21_update")
    lo = float(2 ** (bits - 1) - 1)
    m, w = u.shape
    rows = w // 128
    if impl == "xla":
        d = (u.astype(jnp.float32) - u_hat.astype(jnp.float32)).reshape(m, rows, 128)
        rowmax = jnp.max(jnp.abs(d), axis=-1)
        scales = _ef21_row_scales(rowmax, leaf_rows, lo)[..., None]
        q = jnp.clip(jnp.round(d / scales), -lo, lo)
        out = u_hat.astype(jnp.float32).reshape(m, rows, 128) + q * scales
        return out.reshape(m, w).astype(u.dtype)
    from repro.kernels import round_tail as rt

    interp = impl == "pallas_interpret"
    rowmax = per_client(
        lambda u, u_hat: rt.ef21_rowmax_pallas(u, u_hat, block=block,
                                               interpret=interp),
        (u, u_hat))
    scales = _ef21_row_scales(rowmax, leaf_rows, lo)
    return per_client(
        lambda u, u_hat, scales: rt.ef21_apply_pallas(
            u, u_hat, scales, bits, block=block, interpret=interp),
        (u, u_hat, scales))


# ---------------------------------------------------------------------------
# cohort row movement (core.api cohort engine): gather the active rows out
# of the population arena, scatter the updated rows back
# ---------------------------------------------------------------------------

@_scoped
def row_gather(arr, idx, *, impl: Optional[str] = None, block: Optional[int] = None):
    """Cohort gather out[t] = arr[idx[t]]: arr (m, width), idx (m_active,)
    int row ids.  One read of the gathered rows + one write of the
    (m_active, width) cohort buffer; the Pallas path rides a scalar-prefetch
    input index map (no materialised permutation)."""
    impl = _resolve(impl, "row_gather")
    if impl == "xla":
        return jnp.take(arr, idx, axis=0)
    from repro.kernels import gather as gk

    return gk.row_gather_pallas(arr, idx, block=block,
                                interpret=(impl == "pallas_interpret"))


@_scoped
def row_scatter(dst, idx, rows, *, impl: Optional[str] = None,
                block: Optional[int] = None):
    """Cohort scatter: returns dst with dst[idx[t]] = rows[t] (idx unique --
    the participation draw never repeats a client).  The XLA path is a plain
    unique-index scatter (in place when dst is donated); the Pallas path
    re-phrases it as a population-grid gather through the inverse position
    table pos[idx[t]] = t with a keep-mask at silent rows, so every output
    row is written exactly once and no input/output aliasing is needed."""
    impl = _resolve(impl, "row_scatter")
    if impl == "xla":
        return dst.at[idx].set(rows, unique_indices=True)
    from repro.kernels import gather as gk

    m = dst.shape[0]
    mc = idx.shape[0]
    pos = jnp.zeros((m,), jnp.int32).at[idx].set(
        jnp.arange(mc, dtype=jnp.int32), unique_indices=True)
    mask = jnp.zeros((m,), jnp.int32).at[idx].set(1, unique_indices=True)
    return gk.row_scatter_pallas(dst, pos, mask, rows, block=block,
                                 interpret=(impl == "pallas_interpret"))


# ---------------------------------------------------------------------------
# graph-PDMM neighbor reduce + directed dual flip over the edge-dual arena
# (core.topology layout: (2|E|, width) directed duals, width % 128 == 0)
# ---------------------------------------------------------------------------

@_scoped
def neighbor_reduce(z, *, seg, first, sgn, n: int,
                    impl: Optional[str] = None, block: Optional[int] = None):
    """Per-node dual offsets s_i = sum_{j in N(i)} A_{ij} z_{i|j}.

    z: (2E, width) edge-dual arena; seg/first/sgn: (2E,) static slot tables
    (``Topology``: segment id = slot owner, segment-start flag, constraint
    sign).  Node i's slots are contiguous, so the XLA reference is a sorted
    segment-sum; the Pallas kernel fuses the sign apply + reduction into one
    pass with the output row resident in VMEM across each segment."""
    impl = _resolve(impl, "neighbor_reduce")
    if impl == "xla":
        zf = z.astype(jnp.float32)
        signed = jnp.where(jnp.asarray(sgn)[:, None] >= 0, zf, -zf)
        out = jax.ops.segment_sum(
            signed, jnp.asarray(seg), num_segments=n, indices_are_sorted=True
        )
        return out.astype(z.dtype)
    from repro.kernels import neighbor_reduce as nr

    return nr.neighbor_reduce_pallas(
        z, seg, first, sgn, n, block=block,
        interpret=(impl == "pallas_interpret"),
    )


@_scoped
def edge_flip(z, x, c, *, rev, nbr, sgn, mask=None,
              impl: Optional[str] = None, block: Optional[int] = None):
    """PDMM's directed dual exchange, written at the receiving slot:

        z'[slot(j|i)] = z[slot(i|j)] + 2 c A_{ij} x_i
                      = z[rev[t]] - 2 c sgn[t] x[nbr[t]]

    (A_{ij} here carries i = nbr[t], j = src[t], so A_{ij} = sgn[rev[t]] =
    -sgn[t].)

    z: (2E, width); x: (n, width) node-primal rows; rev/nbr/sgn: (2E,)
    static slot tables.  ``mask`` (optional (2E,) bool/int, 1 = the sending
    node ``nbr[t]`` fired) keeps z[t] at silent slots -- the stochastic
    node-firing / color-schedule variant.  One pass; both gathers ride the
    Pallas scalar-prefetch index maps (no materialised z[rev] copy)."""
    impl = _resolve(impl, "edge_flip")
    if impl == "xla":
        zf = z.astype(jnp.float32)
        flip = (zf[jnp.asarray(rev)]
                - (2.0 * c) * jnp.asarray(sgn, jnp.float32)[:, None]
                * x.astype(jnp.float32)[jnp.asarray(nbr)])
        if mask is not None:
            flip = jnp.where(jnp.asarray(mask)[:, None] != 0, flip, zf)
        return flip.astype(z.dtype)
    from repro.kernels import neighbor_reduce as nr

    return nr.edge_flip_pallas(
        z, x, c, rev, nbr, sgn,
        mask=None if mask is None else jnp.asarray(mask, jnp.int32),
        block=block, interpret=(impl == "pallas_interpret"),
    )


# ---------------------------------------------------------------------------
# rg-lru recurrence
# ---------------------------------------------------------------------------

def lru_scan(a, b, h0, *, chunk: int = 512):
    """Linear recurrence h_t = a_t h_{t-1} + b_t; a, b: (B, S, D), h0 (B, D).

    Chunked: an outer ``lax.scan`` over S/chunk carries the boundary state and
    an inner associative scan runs within each chunk.  A monolithic
    associative scan over the full sequence materialises O(log S) full-size
    f32 intermediates -- at 32k x 4096 that alone was tens of GiB/device.
    """
    B, S, D = a.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    af = a.astype(jnp.float32).reshape(B, n, chunk, D)
    bf = b.astype(jnp.float32).reshape(B, n, chunk, D)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inp):
        ac, bc = inp  # (B, chunk, D)
        bc = bc.at[:, 0].add(ac[:, 0] * h)
        _, hs = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        return hs[:, -1], hs

    h_last, ys = jax.lax.scan(
        chunk_step, h0.astype(jnp.float32), (jnp.moveaxis(af, 1, 0), jnp.moveaxis(bf, 1, 0))
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, D)
    return y.astype(a.dtype), h_last
