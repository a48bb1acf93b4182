"""FedAvg (McMahan et al. 2017) -- the weakest baseline in the paper's
experiments: plain local SGD + parameter averaging, no dual/control state, so
it drifts under client heterogeneity when K > 1 (paper Fig. 2).

Arena fast path (``core.arena``): the K local-SGD steps share SCAFFOLD's
offset inner loop with the correction disabled -- affine oracles run the
WHOLE loop as one fused K-step kernel (lam-free, rho = 0), arena-native
oracles scan lam-free fused arena updates -- and the round tail is the
single uplink mean.  Plain FedAvg carries NO per-client state; the EF21 /
partial-participation variants add the arena-resident ``u_hat`` server view
(same cache contract as GPDMM: silent clients' cached uplink is reused, the
EF21 integrator accumulates quantised deltas), donated in place.
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
    FedOpt, cohort_batch, map_clients, run_cohort_inner, use_arena,
    use_cohort,
)
from repro.core.gpdmm import _eta_val, _step_for, arena_metrics
from repro.core.scaffold import inner_steps_plain_arena
from repro.core.uplink import admit, participation_key
from repro.kernels import ops


def _cohort_steps(cfg: FederatedConfig, spec, grad_fn, per_step, x_s_row,
                  batch_c, idx):
    """The cohort's plain K-step loop from the server row, tiled by
    ``cohort_tile`` when set; shared by the device cohort round and the
    popstore body."""
    eta = _eta_val(cfg.eta)
    per_client = np.ndim(eta) > 0

    def inner(rows, b):
        eta_t = rows[0] if per_client else eta  # tiled with the batch
        mc = jax.tree.leaves(b)[0].shape[1 if per_step else 0]
        x0 = jnp.broadcast_to(x_s_row[None], (mc, spec.width))
        return inner_steps_plain_arena(
            spec, grad_fn, x0, x_s_row, b, K=cfg.inner_steps, eta=eta_t,
            per_step=per_step,
        )

    rows = (jnp.asarray(eta)[idx],) if per_client else ()
    return run_cohort_inner(cfg, inner, rows, batch_c, per_step=per_step)


def popstore_body(cfg: FederatedConfig, spec, m: int, grad_fn, per_step):
    """Device half of a host-popstore FedAvg round (see gpdmm.popstore_body):
    the cohort runs the plain K-step loop from the server row; only the
    staged ``u_hat`` rows (EF21 integrator / silence fallback) move, and the
    host driver maintains the population mean incrementally."""

    def body(server, staged, idx, round_idx, batch):
        x_s_row = spec.pack(server["x_s"])
        x_K = _cohort_steps(cfg, spec, grad_fn, per_step, x_s_row,
                            cohort_batch(batch, idx, m, per_step), idx)
        adm = admit(cfg, x_K, arena=spec, round_idx=round_idx, m=m,
                    ref=x_s_row, fallback=staged["u_hat"], idx=idx)
        return ({"u_hat": adm.admitted}, {},
                arena_metrics(adm, x_K, x_s_row))

    return body


def _num_clients(state, batch, per_step_batches):
    """Plain FedAvg keeps no per-client state, so the client count comes
    from the batch layout ((m, ...) or (K, m, ...)); the EF21/partial
    variants carry u_hat and read m off it."""
    u_hat = state.get("u_hat")
    if u_hat is not None:
        return jax.tree.leaves(u_hat)[0].shape[0]
    b0 = jax.tree.leaves(batch)[0]
    return b0.shape[1] if per_step_batches else b0.shape[0]


def _round_arena_cohort(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """FedAvg round over the sampled cohort (see gpdmm._round_arena_cohort):
    no per-client optimiser rows move at all -- the cohort runs the plain
    K-step loop from the server row, the uplink scatters into the
    arena-resident u_hat cache, and the server mean over the scattered
    buffer realises (sum_active x_K + sum_silent u_hat) / m exactly as the
    masked path's mean-of-selected-rows."""
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    u_hat = state["u_hat"]  # guaranteed: participation < 1 carries the cache
    m = u_hat.shape[0]
    x_s_row = spec.pack(state["x_s"])
    idx, _mask = T.cohort_indices(
        participation_key(cfg, state["round"]), m, cfg.participation
    )
    x_K = _cohort_steps(cfg, spec, grad_fn, per_step_batches, x_s_row,
                        cohort_batch(batch, idx, m, per_step_batches), idx)
    adm = admit(cfg, x_K, arena=spec, round_idx=state["round"], m=m,
                ref=x_s_row, fallback=ops.row_gather(u_hat, idx), idx=idx)
    u_hat_new = ops.row_scatter(u_hat, idx, adm.admitted)
    x_s_new = jnp.mean(u_hat_new, axis=0)  # <- the round's single all-reduce
    new_state = {
        "u_hat": u_hat_new,
        "x_s": spec.unpack(x_s_new),
        "round": state["round"] + 1,
    }
    return new_state, arena_metrics(adm, x_K, x_s_row)


def _round_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    K, eta = cfg.inner_steps, cfg.eta
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    m = _num_clients(state, batch, per_step_batches)
    if use_cohort(cfg, m):
        return _round_arena_cohort(cfg, state, grad_fn, batch, per_step_batches)
    x_s_row = spec.pack(state["x_s"])
    x0 = jnp.broadcast_to(x_s_row[None], (m, spec.width))

    x_K = inner_steps_plain_arena(
        spec, grad_fn, x0, x_s_row, batch, K=K, eta=eta, per_step=per_step_batches,
    )
    adm = admit(cfg, x_K, arena=spec, round_idx=state["round"], m=m,
                ref=x_s_row, fallback=state.get("u_hat"), state=state)
    x_s_new = jnp.mean(adm.admitted, axis=0)  # <- the round's single all-reduce
    new_state = adm.state | {"x_s": spec.unpack(x_s_new),
                             "round": state["round"] + 1}
    if "u_hat" in state:
        new_state["u_hat"] = adm.admitted
    return new_state, arena_metrics(adm, x_K, x_s_row)


def _round(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False):
    if use_arena(cfg, state["x_s"]):
        return _round_arena(cfg, state, grad_fn, batch, per_step_batches)
    K, eta = cfg.inner_steps, _eta_val(cfg.eta)
    x_s = state["x_s"]
    m = _num_clients(state, batch, per_step_batches)
    x_s_b = T.tree_broadcast(x_s, m)
    vgrad = partial(map_clients, grad_fn)

    def one_step(x, xs_k):
        b = xs_k if per_step_batches else batch
        g = vgrad(x, b)
        # plain SGD step: lam-free fused update with rho = 0 (xs unused)
        x_new = T.tmap(lambda xx, gg: ops.fused_update(
            xx, gg, xx, None, _step_for(eta, xx), 0.0), x, g)
        return x_new, None

    if per_step_batches:
        x_K, _ = jax.lax.scan(one_step, x_s_b, batch)
    else:
        x_K, _ = jax.lax.scan(one_step, x_s_b, None, length=K)

    adm = admit(cfg, x_K, arena=None, round_idx=state["round"], m=m, ref=x_s,
                fallback=state.get("u_hat"), state=state)
    new_state = adm.state | {"x_s": T.tree_client_mean(adm.admitted),
                             "round": state["round"] + 1}
    if "u_hat" in state:
        new_state["u_hat"] = adm.admitted  # the server's per-client view
    metrics = {
        # silent clients' x_K never enters the state: average the active set
        "client_drift": T.masked_client_mean(
            T.tree_client_sqnorms(T.tree_sub(x_K, x_s_b)), adm.mask),
        "used_arena": jnp.zeros((), jnp.float32),
    } | adm.metrics
    return new_state, metrics


def make(cfg: FederatedConfig) -> FedOpt:
    def init(params, m):
        needs_cache = (cfg.uplink_bits is not None or cfg.participation < 1.0
                       or faults.needs_cache(cfg))
        if use_arena(cfg, params):
            st = {"x_s": params, "round": jnp.zeros((), jnp.int32)}
            spec = arena.ArenaSpec.from_tree(params)
            if needs_cache:
                row = spec.pack(params)
                # server's cached per-client view: init == the round-0 uplink
                # from a client that never moved
                st["u_hat"] = jnp.broadcast_to(row[None], (m, spec.width))
            if faults.async_on(cfg):
                st |= staleness.init_arena(spec, m)
            return st
        st = {"x_s": params, "round": jnp.zeros((), jnp.int32)}
        if needs_cache:
            st["u_hat"] = T.tree_broadcast(params, m)
        if faults.async_on(cfg):
            st |= staleness.init_tree(params, m)
        return st

    return FedOpt(
        name="fedavg",
        init=init,
        round=partial(_round, cfg),
        server_params=lambda s: s["x_s"],
    )
