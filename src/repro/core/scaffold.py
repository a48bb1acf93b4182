"""SCAFFOLD (Karimireddy et al. 2020), eqs. (29)-(30) of the paper, as the
primary baseline.  Control variates c (server) and c_i (clients) compensate
client heterogeneity; both directions transmit TWO variables per round
(x and c), which is the communication contrast with GPDMM the paper draws.

    x_i^{r,0}   = x_s^r
    x_i^{r,k+1} = x_i^{r,k} - eta (grad f_i(x_i^{r,k}) - c_i^r + c^r)
    c_i^{r+1}   = c_i^r - c^r + (x_s^r - x_i^{r,K}) / (K eta)
    x_s^{r+1}   = x_s^r + eta_g mean_i (x_i^{r,K} - x_s^r)   (all-reduce #1)
    c^{r+1}     = c^r + mean_i (c_i^{r+1} - c_i^r)           (all-reduce #2)

Arena fast path (``core.arena``): ``c_i`` is arena-RESIDENT -- it enters and
leaves the round as one ``(m, width)`` buffer donated in place, exactly like
GPDMM's ``lam_s``.  The K inner steps resolve through the ``core.api``
oracle protocol: for affine oracles the control-variate correction
``- c_i + c`` folds into the affine constant (``c`` into the fresh constant,
``c_i`` as the kernel's per-client offset row), so the WHOLE inner loop
stays the single fused K-step kernel with zero extra HBM materialisation;
otherwise a scan of lam-carried fused arena updates runs with rho = 0.  The
round tail is one fused control-variate kernel (``ops.scaffold_cv``) plus
the TWO server all-reduces (x-mean and c-delta-mean) -- the two-variable
communication pattern the paper contrasts with GPDMM's one.

Partial participation (``cfg.participation < 1``, mask drawn from the
``FederatedConfig.seed`` contract like every other algorithm): silent
clients transmit NOTHING, so their deltas contribute zero to both server
means and their c_i is kept -- the server-side invariant c = mean_i c_i
survives partial rounds exactly.  EF21 uplink quantisation is NOT offered
for SCAFFOLD: its uplink is two coupled variables per round and a single
error-feedback integrator per client does not apply; ``make`` rejects the
combination loudly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederatedConfig
from repro.core import arena, faults, staleness
from repro.core import tree_util as T
from repro.core.api import (
    FedOpt, affine_case, arena_grad, cohort_batch, map_clients,
    run_cohort_inner, use_arena, use_cohort,
)
from repro.core.gpdmm import _eta_val, _kept, _step_for, arena_metrics
from repro.core.uplink import admit, participation_key
from repro.kernels import ops


def inner_steps_plain_arena(spec, grad_fn, x0, x_s_row, batch, *, K, eta,
                            per_step, c_i=None, c_row=None):
    """K plain gradient steps over the arena with an optional control-variate
    correction:  x <- x - eta (grad f_i(x) - c_i + c).

    Shared by SCAFFOLD (``c_i``/``c_row`` set) and FedAvg (no correction).
    Resolution, fastest first (the ``core.api`` oracle protocol):

      1. ``affine_arena`` + width fits VMEM: ONE fused K-step kernel.  The
         server variate folds into the (freshly built) affine constant and
         the arena-resident ``c_i`` buffer rides as the kernel's per-client
         offset row -- the correction costs zero extra HBM traffic.
      2. otherwise: a scan of lam-free (FedAvg) or lam-carried (SCAFFOLD,
         lam = c - c_i materialised ONCE per round) fused arena updates with
         rho = 0, the gradient via ``arena_grad`` (arena-native oracles pay
         zero boundary passes).

    ``eta`` may be a scalar, the per-client tuple (auto-eta), or an
    already-gathered per-cohort row -- array forms ride the kernels as a
    per-client stepsize operand (``kernels/ops``).
    """
    eta = _eta_val(eta)
    affine = affine_case(grad_fn, spec, per_step=per_step)
    if affine is not None:
        H, c = affine(spec, batch)
        off = None
        if c_i is not None:
            # grad - c_i + c == H x - ((c_aff - c) + c_i): server variate
            # into the constant, client variate as the offset row
            c = c - c_row[None]
            off = c_i
        x_K, _ = ops.inner_loop_affine(x0, H, c, x_s_row, None, eta, 0.0, K, off=off)
        return x_K

    grad_a, _native = arena_grad(grad_fn, spec)
    lam = None if c_i is None else c_row[None] - c_i  # one (m, width) pass

    def one_step(x, xs_k):
        b = xs_k if per_step else batch
        g = grad_a(x, b)
        # eq. (20) with rho = 0: x - eta (g + lam), lam = c - c_i
        return ops.fused_update_arena(x, g, x_s_row, lam, eta, 0.0), None

    if per_step:
        x_K, _ = jax.lax.scan(one_step, x0, batch)
    else:
        x_K, _ = jax.lax.scan(one_step, x0, None, length=K)
    return x_K


def _cohort_round(cfg: FederatedConfig, spec, grad_fn, per_step, m, x_s_row,
                  c_row, c_i_c, batch_c, idx, round_idx):
    """SCAFFOLD over one cohort, shared by the device cohort round and the
    popstore body: the offset inner loop, the fused control-variate refresh
    and both server all-reduces over the cohort's deltas.  Silent clients
    transmit nothing, so both server means decompose as sum_active(delta) /
    m -- the same zero-delta contract the masked path realises with selects
    (equal at f32: the masked path subtracts the server row back out of the
    mean, this path never adds it in).  Returns ``(c_i_new_c, x_s_new_row,
    c_new_row, x_K, admission)``."""
    K, eta = cfg.inner_steps, _eta_val(cfg.eta)
    per_client = np.ndim(eta) > 0
    eta_c = jnp.asarray(eta)[idx] if per_client else None

    def inner(rows, b):
        ci_t = rows[0]
        eta_t = rows[1] if per_client else eta  # tiled with the state rows
        x0 = jnp.broadcast_to(x_s_row[None], ci_t.shape)
        return inner_steps_plain_arena(
            spec, grad_fn, x0, x_s_row, b, K=K, eta=eta_t,
            per_step=per_step, c_i=ci_t, c_row=c_row,
        )

    rows = (c_i_c,) + ((eta_c,) if per_client else ())
    x_K = run_cohort_inner(cfg, inner, rows, batch_c, per_step=per_step)
    # the wire corrupts the transmitted packet x_i^{r,K}; both uplinked
    # variables (dx_i and dc_i) derive from it, so both see the corruption
    adm = admit(cfg, x_K, arena=spec, round_idx=round_idx, m=m, ref=x_s_row,
                fallback=x_s_row, idx=idx)
    # fused per-cohort tail: c_i' = c_i - c + (x_s - x_t)/(K eta_i)
    alpha = 1.0 / (K * (eta_c if per_client else eta))
    c_i_new_c = _kept(adm.mask,
                      ops.scaffold_cv(c_i_c, adm.sent, c_row, x_s_row, alpha),
                      c_i_c)
    # server: TWO all-reduces over the cohort's deltas (silent rows are zero)
    f32 = jnp.float32
    inv_m = 1.0 / m
    x_s_new = x_s_row + cfg.eta_g * inv_m * jnp.sum(
        (adm.admitted - x_s_row[None]).astype(f32), axis=0).astype(x_s_row.dtype)
    c_new = c_row + inv_m * jnp.sum(
        (c_i_new_c - c_i_c).astype(f32), axis=0).astype(c_row.dtype)
    return c_i_new_c, x_s_new, c_new, x_K, adm


def popstore_body(cfg: FederatedConfig, spec, m: int, grad_fn, per_step):
    """Device half of a host-popstore SCAFFOLD round (see
    gpdmm.popstore_body): the cohort's ``c_i`` rows stage from the host
    store.  Unlike GPDMM's, SCAFFOLD's cohort server update is ALREADY
    O(cohort) on device (both all-reduces are sums over cohort deltas), so
    this body computes the new server rows itself -- bit-identical to
    ``_round_arena_cohort`` -- and returns them in ``server_rows``; only the
    ``c_sum_norm`` diagnostic needs the host driver's incremental
    ``sum(c_i)``."""

    def body(server, staged, idx, round_idx, batch):
        x_s_row = spec.pack(server["x_s"])
        c_i_new_c, x_s_new, c_new, x_K, adm = _cohort_round(
            cfg, spec, grad_fn, per_step, m, x_s_row, spec.pack(server["c"]),
            staged["c_i"], cohort_batch(batch, idx, m, per_step), idx,
            round_idx)
        return ({"c_i": c_i_new_c}, {"x_s": x_s_new, "c": c_new},
                arena_metrics(adm, x_K, x_s_row))

    return body


def _round_arena_cohort(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """SCAFFOLD round over the sampled cohort (see gpdmm._round_arena_cohort):
    the cohort's c_i rows gather, run the offset inner loop + fused
    control-variate refresh (``_cohort_round``), and scatter back."""
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    c_i = state["c_i"]
    m = c_i.shape[0]
    x_s_row = spec.pack(state["x_s"])
    idx, _mask = T.cohort_indices(
        participation_key(cfg, state["round"]), m, cfg.participation
    )
    c_i_new_c, x_s_new, c_new, x_K, adm = _cohort_round(
        cfg, spec, grad_fn, per_step_batches, m, x_s_row,
        spec.pack(state["c"]), ops.row_gather(c_i, idx),
        cohort_batch(batch, idx, m, per_step_batches), idx, state["round"])
    c_i_new = ops.row_scatter(c_i, idx, c_i_new_c)  # silent clients keep c_i

    new_state = {
        "x_s": spec.unpack(x_s_new),
        "c": spec.unpack(c_new),
        "c_i": c_i_new,
        "round": state["round"] + 1,
    }
    return new_state, {
        "c_sum_norm": jnp.linalg.norm(
            jnp.sum((c_i_new - c_new[None]).astype(jnp.float32), axis=0)),
    } | arena_metrics(adm, x_K, x_s_row)


def _round_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """SCAFFOLD round over the flat arena: fused K-step inner loop with the
    control-variate offset, ONE fused c_i refresh, and the two server
    all-reduces.  ``c_i`` is arena-resident; only the server-sized x_s and c
    rows (1/m of the state) repack per round."""
    K, eta = cfg.inner_steps, _eta_val(cfg.eta)
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    c_i = state["c_i"]  # arena-resident (m, width)
    m = c_i.shape[0]
    if use_cohort(cfg, m):
        return _round_arena_cohort(cfg, state, grad_fn, batch, per_step_batches)
    x_s_row = spec.pack(state["x_s"])
    c_row = spec.pack(state["c"])
    x0 = jnp.broadcast_to(x_s_row[None], (m, spec.width))

    x_K = inner_steps_plain_arena(
        spec, grad_fn, x0, x_s_row, batch, K=K, eta=eta,
        per_step=per_step_batches, c_i=c_i, c_row=c_row,
    )

    # the wire corrupts the transmitted packet x_i^{r,K}; both uplinked
    # variables (dx_i and dc_i) derive from it, so both see the corruption.
    # Silent/demoted clients transmit nothing: the zero-delta server row
    # stands in, and under the staleness engine a buffered row lands s
    # rounds later and mixes toward it with weight gamma**s
    adm = admit(cfg, x_K, arena=spec, round_idx=state["round"], m=m,
                ref=x_s_row, fallback=x_s_row, state=state)
    # fused per-client tail: c_i' = c_i - c + (x_s - x_t)/(K eta_i); the
    # control variate refreshes on FRESH participation only -- an arriving
    # stale row carries no variate update
    c_i_new = _kept(adm.mask,
                    ops.scaffold_cv(c_i, adm.sent, c_row, x_s_row, 1.0 / (K * eta)),
                    c_i)
    # server: TWO all-reduces (x-delta and c-delta)
    x_s_new = x_s_row + cfg.eta_g * (jnp.mean(adm.admitted, axis=0) - x_s_row)
    c_new = c_row + jnp.mean(c_i_new - c_i, axis=0)

    new_state = adm.state | {
        "x_s": spec.unpack(x_s_new),  # server-sized; clients stay packed
        "c": spec.unpack(c_new),
        "c_i": c_i_new,
        "round": state["round"] + 1,
    }
    return new_state, {
        # invariant: sum_i (c_i - c) = 0 given zero init (padding is zero on
        # both sides, so no masking is needed)
        "c_sum_norm": jnp.linalg.norm(
            jnp.sum((c_i_new - c_new[None]).astype(jnp.float32), axis=0)),
    } | arena_metrics(adm, x_K, x_s_row)


def _round(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False):
    if use_arena(cfg, state["x_s"]):
        return _round_arena(cfg, state, grad_fn, batch, per_step_batches)
    K, eta = cfg.inner_steps, _eta_val(cfg.eta)
    x_s, c, c_i = state["x_s"], state["c"], state["c_i"]
    m = jax.tree.leaves(c_i)[0].shape[0]
    x_s_b = T.tree_broadcast(x_s, m)
    c_b = T.tree_broadcast(c, m)
    # lam := c - c_i enters the shared fused step with rho = 0
    lam = T.tree_sub(c_b, c_i)
    vgrad = partial(map_clients, grad_fn)

    def one_step(x, xs_k):
        b = xs_k if per_step_batches else batch
        g = vgrad(x, b)
        x_new = T.tmap(lambda xx, gg, ll: ops.fused_update(
            xx, gg, xx, ll, _step_for(eta, xx), 0.0), x, g, lam)
        return x_new, None

    if per_step_batches:
        x_K, _ = jax.lax.scan(one_step, x_s_b, batch)
    else:
        x_K, _ = jax.lax.scan(one_step, x_s_b, None, length=K)

    # same admission contract as the arena path: x_s_b is the zero-delta
    # fallback, c_i refreshes on fresh participation only
    adm = admit(cfg, x_K, arena=None, round_idx=state["round"], m=m, ref=x_s,
                fallback=x_s_b, state=state)
    # multiply by the precomputed 1/(K eta), NOT divide by (K eta): the same
    # rounding as the fused arena kernel, so the parity tests compare paths
    # at f32 resolution instead of absorbing a divide-vs-multiply ulp
    alpha = 1.0 / (K * eta)
    c_i_new = T.tmap(
        lambda ci, cc, s, xk: ci - cc + (s - xk) * _step_for(alpha, xk),
        c_i, c_b, x_s_b, adm.sent)
    if adm.mask is not None:
        c_i_new = T.tree_select(adm.mask, c_i_new, c_i)
    # server: TWO all-reduces (x-delta and c-delta)
    dx = T.tree_client_mean(T.tree_sub(adm.admitted, x_s_b))
    dc = T.tree_client_mean(T.tree_sub(c_i_new, c_i))
    x_s_new = T.tree_axpy(cfg.eta_g, dx, x_s)
    c_new = T.tree_add(c, dc)

    new_state = adm.state | {
        "x_s": x_s_new,
        "c": c_new,
        "c_i": c_i_new,
        "round": state["round"] + 1,
    }
    metrics = {
        # invariant: sum_i (c_i - c) = 0 given zero init
        "c_sum_norm": T.tree_norm(T.tree_client_sum(T.tree_sub(c_i_new, T.tree_broadcast(c_new, m)))),
        # silent clients' x_K never enters the state: average the active set
        "client_drift": T.masked_client_mean(
            T.tree_client_sqnorms(T.tree_sub(x_K, x_s_b)), adm.mask),
        "used_arena": jnp.zeros((), jnp.float32),
    } | adm.metrics
    return new_state, metrics


def make(cfg: FederatedConfig) -> FedOpt:
    if cfg.uplink_bits is not None:
        raise NotImplementedError(
            "SCAFFOLD+EF21 (uplink_bits is not None) is not supported: each "
            "SCAFFOLD round uplinks two coupled variables per client -- the "
            "model delta dx_i = x_i^{r,K} - x_s^r and the control-variate "
            "delta dc_i = c_i^{r+1} - c_i^r = (x_s^r - x_i^{r,K})/(K eta) - "
            "c^r.  EF21 integrates ONE error-feedback state u_hat_i per "
            "client; quantising dx_i alone desynchronises the server's c = "
            "mean_i c_i invariant, and a second integrator for dc_i is NOT "
            "error-feedback (dc_i is a function of dx_i, so the two "
            "quantisation errors are coupled).  Use algorithm='gpdmm' (one "
            "uplink variable, EF21 supported) or drop uplink_bits."
        )

    def init(params, m):
        if use_arena(cfg, params):
            # arena-resident control variates: one (m, width) buffer donated
            # in place round over round; x_s and c stay pytrees (the public
            # server-params / server-variate contract, p_shard in launchers)
            spec = arena.ArenaSpec.from_tree(params)
            st = {
                "x_s": params,
                "c": T.tree_zeros_like(params),
                "c_i": arena.zeros(spec, m),
                "round": jnp.zeros((), jnp.int32),
            }
            if faults.async_on(cfg):
                st |= staleness.init_arena(spec, m)
            return st
        st = {
            "x_s": params,
            "c": T.tree_zeros_like(params),
            "c_i": T.tree_zeros_like(T.tree_broadcast(params, m)),
            "round": jnp.zeros((), jnp.int32),
        }
        if faults.async_on(cfg):
            st |= staleness.init_tree(params, m)
        return st

    return FedOpt(
        name="scaffold",
        init=init,
        round=partial(_round, cfg),
        server_params=lambda s: s["x_s"],
    )
