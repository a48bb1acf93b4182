"""GPDMM (arXiv:2107.08809, Algorithm 1) in plain ``jax.numpy``.

One round, for clients i = 1..m with K inner steps, rho = 1/(K eta) and
step = 1/(1/eta + rho):

    x_i^{k+1} = x_i^k - step (grad f_i(x_i^k) + rho (x_i^k - x_s) + lam_i)
    xbar_i    = mean_k x_i^{k+1}                               (eq. 23)
    lam_is    = rho (x_s - xbar_i) - lam_i
    u_i       = xbar_i - lam_is / rho                          (uplink)
    x_s'      = mean_i u_i                                     (server)
    lam_i'    = rho (u_i - x_s')                               (dual refresh)
    x_i      <- x_i^K

All arithmetic is float32.  Every variable the algorithm keeps between steps
(x_i^k, xbar_i, u_i, x_s, lam_i) is stored in ``store``, the precision the
configuration states for its state.  Client state is a pytree stacked with a
leading client dimension.  Clients run one after another (``client_batch``
at a time), so that one client's float32 temporaries are live at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def init(x0, m: int):
    """Round-0 state: every client starts at the server point, duals at 0."""
    return {"x_s": _tmap(jnp.copy, x0),
            "x_c": _tmap(lambda a: jnp.broadcast_to(a[None], (m,) + a.shape), x0),
            "lam": _tmap(lambda a: jnp.zeros((m,) + a.shape, a.dtype), x0)}


def round_fn(state, batches, grad_one, *, K: int, eta: float, per_step: bool,
             store, client_batch: int = 1):
    """One GPDMM round.  ``batches`` leaves lead with the client dim, then K
    when ``per_step`` (else one batch serves all K steps).  ``grad_one(x,
    batch)`` is one client's float32 gradient.  Returns (state', drift) with
    drift = mean_i ||x_i^K - x_s||^2 over the round's starting x_s.

    Clients run ``client_batch`` at a time, each block's x_i^K and u_i
    written in place over its x_i and lam_i, so that a donated state is
    updated without a second copy."""
    rho = 1.0 / (K * eta)
    step = 1.0 / (1.0 / eta + rho)
    x_s = state["x_s"]
    m = jax.tree.leaves(state["x_c"])[0].shape[0]
    n_blocks = m // client_batch

    def block(a, j):
        return jax.lax.dynamic_slice_in_dim(a, j * client_batch, client_batch, 0)

    def client(x, lam, b):
        acc = None
        for k in range(K):  # unrolled: K is small, and no loop carry is copied
            bk = jax.tree.map(lambda t: t[k], b) if per_step else b
            xf = _tmap(lambda a: a.astype(F32), x)
            g = grad_one(xf, bk)
            x = _tmap(lambda a, gg, s, l: (a - step * (
                gg + rho * (a - s.astype(F32)) + l.astype(F32))).astype(store),
                xf, g, x_s, lam)
            xf = _tmap(lambda a: a.astype(F32), x)
            acc = xf if acc is None else _tmap(jnp.add, acc, xf)
        x_k = x
        xbar = _tmap(lambda a: (a / K).astype(store).astype(F32), acc)
        up = _tmap(lambda xb, s, l: (xb - (rho * (s.astype(F32) - xb)
                                           - l.astype(F32)) / rho).astype(store),
                   xbar, x_s, lam)
        drift = sum(jnp.sum(jnp.square(a.astype(F32) - s.astype(F32)))
                    for a, s in zip(jax.tree.leaves(x_k), jax.tree.leaves(x_s)))
        return x_k, up, drift

    def body(j, carry):
        x_c, lam, drift = carry
        xj, lj = _tmap(lambda a: block(a, j), x_c), _tmap(lambda a: block(a, j), lam)
        bj = jax.tree.map(lambda a: block(a, j), batches)
        x_k, up, d = jax.vmap(client)(xj, lj, bj)
        put = lambda a, v: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            a, v, j * client_batch, 0)
        return _tmap(put, x_c, x_k), _tmap(put, lam, up), drift + jnp.sum(d)

    x_k, up, drift = jax.lax.fori_loop(
        0, n_blocks, body, (state["x_c"], state["lam"], jnp.zeros((), F32)))
    x_s_new = _tmap(lambda u: jnp.mean(u.astype(F32), axis=0).astype(store), up)
    lam_new = _tmap(lambda u, s: (rho * (u.astype(F32) - s.astype(F32)[None])
                                  ).astype(store), up, x_s_new)
    return {"x_s": x_s_new, "x_c": x_k, "lam": lam_new}, drift / m
