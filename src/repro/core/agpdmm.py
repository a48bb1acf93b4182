"""AGPDMM (Algorithm 2, Zhang et al. 2021): accelerated GPDMM.

Differences from GPDMM (Alg. 1):
  * client init x_i^{r,0} = x_s^r (the fresher global estimate), so no
    per-client primal carry is stored;
  * the dual update uses the LAST iterate x_i^{r,K} (eq. 24), not the average.

The paper states AGPDMM transmits two variables server->client (x_s and
lam_{s|i}).  In the SPMD mapping the downlink lam_{s|i}^{r+1} =
rho (x_i^{r,K} - x_s^{r+1}) - lam_{i|s}^{r+1} is recomputed client-locally
from x_s^{r+1} and client-resident quantities, so the realised collective
traffic equals GPDMM's (one all-reduce per round).  This implementation
observation is recorded in EXPERIMENTS.md SSPerf.

When K == 1 and rho = 1/eta, the round reduces exactly to vanilla gradient
descent with stepsize eta (paper eq. (27)); ``tests/test_core.py`` asserts
this identity numerically.
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
    FedOpt, cohort_batch, resolved_rho, run_cohort_inner, use_arena,
    use_cohort,
)
from repro.core.gpdmm import (
    _eta_val, arena_metrics, arena_tail, cohort_tail, inner_steps,
    inner_steps_arena, participation_key, popstore_tail,
)
from repro.kernels import ops


def popstore_body(cfg: FederatedConfig, spec, m: int, grad_fn, per_step):
    """Device half of a host-popstore AGPDMM round (see gpdmm.popstore_body):
    only the ``u_hat`` rows stage -- the client init is the fresh server row
    (no primal carry), and the dual rows reconstruct lazily from the staged
    uplink cache via lam_{s|i} = rho (u_hat_i - x_s)."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    f32 = jnp.float32

    eta_v = _eta_val(cfg.eta)
    per_client = np.ndim(eta_v) > 0

    def body(server, staged, idx, round_idx, batch):
        x_s_row = spec.pack(server["x_s"])
        u_hat_c = staged["u_hat"]
        lam_c = ops.dual_from_uplink(u_hat_c, x_s_row, rho)  # lazy dual
        batch_c = cohort_batch(batch, idx, m, per_step)

        def inner(rows, b):
            lam_t = rows[0]
            eta_t = rows[1] if per_client else eta_v  # tiled with the rows
            x0 = jnp.broadcast_to(x_s_row[None], lam_t.shape)
            return inner_steps_arena(
                spec, grad_fn, x0, x_s_row, lam_t, b, K=K, eta=eta_t,
                rho=rho, per_step=per_step,
                vr_snapshot=x0 if cfg.variance_reduction == "svrg" else None,
                average=False,
            )

        rows = (lam_c,) + ((jnp.asarray(eta_v)[idx],) if per_client else ())
        x_K, _ = run_cohort_inner(cfg, inner, rows, batch_c,
                                  per_step=per_step)
        _, uplink = ops.round_tail(x_K, lam_c, x_s_row, rho,
                                   with_lam_is=False)
        uplink, keep_c, fm = popstore_tail(cfg, spec, x_s_row, u_hat_c,
                                           uplink, idx, round_idx, m)
        metrics = {
            "client_drift": T.masked_client_mean(
                jnp.sum(jnp.square((x_K - x_s_row[None]).astype(f32)),
                        axis=1), keep_c),
            "used_arena": jnp.ones((), f32),
        } | fm
        return {"u_hat": uplink}, {}, metrics

    return body


def _round_arena_cohort(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """AGPDMM round over the sampled cohort (see gpdmm._round_arena_cohort):
    only lam_s rows gather/scatter -- the client init is the fresh server
    row, so there is no primal carry to move at all."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam = state["lam_s"]
    m = lam.shape[0]
    x_s_row = spec.pack(state["x_s"])
    idx, _mask = T.cohort_indices(
        participation_key(cfg, state["round"]), m, cfg.participation
    )
    lam_c = ops.row_gather(lam, idx)
    batch_c = cohort_batch(batch, idx, m, per_step_batches)
    eta_v = _eta_val(cfg.eta)
    per_client = np.ndim(eta_v) > 0

    def inner(rows, b):
        lam_t = rows[0]
        eta_t = rows[1] if per_client else eta_v  # tiled with the state rows
        x0 = jnp.broadcast_to(x_s_row[None], lam_t.shape)
        return inner_steps_arena(
            spec, grad_fn, x0, x_s_row, lam_t, b, K=K, eta=eta_t, rho=rho,
            per_step=per_step_batches,
            vr_snapshot=x0 if cfg.variance_reduction == "svrg" else None,
            average=False,
        )

    rows = (lam_c,) + ((jnp.asarray(eta_v)[idx],) if per_client else ())
    x_K, _ = run_cohort_inner(cfg, inner, rows, batch_c,
                              per_step=per_step_batches)

    _, uplink = ops.round_tail(x_K, lam_c, x_s_row, rho, with_lam_is=False)
    fplan = faults.plan(cfg, state["round"], m)
    new_state, keep_c, fm = cohort_tail(cfg, spec, state, uplink, idx, fplan)
    new_state |= {"round": state["round"] + 1}
    return new_state, arena_metrics(new_state["lam_s"], x_K, x_s_row, keep_c) | fm


def _round_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """AGPDMM round over the flat arena (see gpdmm._round_arena): lam_s and
    u_hat are arena-resident (m, width) buffers; the client init is the
    fresher server row, so no primal carry is stored at all."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam = state["lam_s"]
    m = lam.shape[0]
    if use_cohort(cfg, m):
        return _round_arena_cohort(cfg, state, grad_fn, batch, per_step_batches)
    x_s_row = spec.pack(state["x_s"])
    x0 = jnp.broadcast_to(x_s_row[None], (m, spec.width))

    x_K, _ = inner_steps_arena(
        spec, grad_fn, x0, x_s_row, lam, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches,
        vr_snapshot=x0 if cfg.variance_reduction == "svrg" else None,
        average=False,
    )

    _, uplink = ops.round_tail(x_K, lam, x_s_row, rho, with_lam_is=False)
    new_state, x_s_new, lam_s_new, mask, fm = arena_tail(cfg, spec, state, uplink, m)
    new_state |= {
        "x_s": spec.unpack(x_s_new),
        "lam_s": lam_s_new,
        "round": state["round"] + 1,
    }
    return new_state, arena_metrics(lam_s_new, x_K, x_s_row, mask) | fm


def _round(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False):
    if use_arena(cfg, state["x_s"]):
        return _round_arena(cfg, state, grad_fn, batch, per_step_batches)
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    x_s, lam_s = state["x_s"], state["lam_s"]
    m = jax.tree.leaves(lam_s)[0].shape[0]
    x_s_b = T.tree_broadcast(x_s, m)

    x_K, _ = inner_steps(
        grad_fn, x_s_b, x_s_b, lam_s, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches,
        vr_snapshot=x_s_b if cfg.variance_reduction == "svrg" else None,
    )

    lam_is = T.tmap(lambda s, xk, l: rho * (s - xk) - l, x_s_b, x_K, lam_s)
    uplink = T.tmap(lambda xk, l: xk - l / rho, x_K, lam_is)
    new_state = {}
    if cfg.uplink_bits is not None:  # beyond-paper: EF21 delta-quantised uplink
        uplink = T.tree_quantize_delta(uplink, state["u_hat"], cfg.uplink_bits)
    # robustness layer: inject -> participation -> screen -> combined select
    fplan = faults.plan(cfg, state["round"], m)
    uplink = faults.inject_tree(cfg.faults, fplan, uplink)
    pmask = None
    if cfg.participation < 1.0:  # beyond-paper: async PDMM (partial rounds)
        pmask = T.participation_mask(
            participation_key(cfg, state["round"]), m, cfg.participation
        )
    keep = None
    if faults.screening_on(cfg):
        keep = faults.screen_keep_tree(cfg, uplink, x_s)
    mask = faults.combine_mask(pmask, fplan, keep)
    sm = {}
    if faults.async_on(cfg):
        # bounded-staleness engine: delayed rows buffer, arrivals mix
        uplink, mask, stale_up, sm = staleness.step_tree(
            cfg, fplan, uplink, state["u_hat"], mask, state)
        new_state |= stale_up
    elif mask is not None:
        uplink = T.tree_select(mask, uplink, state["u_hat"])
    if "u_hat" in state:
        new_state["u_hat"] = uplink
    x_s_new = T.tree_client_mean(uplink)
    x_s_new_b = T.tree_broadcast(x_s_new, m)
    # rho (u_i - x_s): reconstructed from the transmitted uplink (see gpdmm)
    lam_s_new = T.tmap(lambda u, s: rho * (u - s), uplink, x_s_new_b)

    new_state |= {"x_s": x_s_new, "lam_s": lam_s_new, "round": state["round"] + 1}
    metrics = {
        "lam_sum_norm": T.tree_norm(T.tree_client_sum(lam_s_new)),
        # silent clients' x_K never enters the state: average the active set
        "client_drift": T.masked_client_mean(
            T.tree_client_sqnorms(T.tree_sub(x_K, x_s_b)), mask),
        "used_arena": jnp.zeros((), jnp.float32),
    }
    if fplan is not None or keep is not None:
        tx = faults.combine_mask(pmask, fplan, None)
        if faults.async_on(cfg):
            tx = staleness.fresh_mask(tx, fplan)
        metrics |= faults.fault_metrics(fplan, tx, keep) | sm
    return new_state, metrics


def make(cfg: FederatedConfig) -> FedOpt:
    def init(params, m):
        if use_arena(cfg, params):
            spec = arena.ArenaSpec.from_tree(params)
            st = {
                "x_s": params,
                "lam_s": arena.zeros(spec, m),
                "round": jnp.zeros((), jnp.int32),
            }
            if (cfg.uplink_bits is not None or cfg.participation < 1.0
                    or faults.needs_cache(cfg)):
                row = spec.pack(params)
                st["u_hat"] = jnp.broadcast_to(row[None], (m, spec.width))
            if faults.async_on(cfg):
                st |= staleness.init_arena(spec, m)
            return st
        st = {
            "x_s": params,
            "lam_s": T.tree_zeros_like(T.tree_broadcast(params, m)),
            "round": jnp.zeros((), jnp.int32),
        }
        if (cfg.uplink_bits is not None or cfg.participation < 1.0
                or faults.needs_cache(cfg)):
            st["u_hat"] = T.tree_broadcast(params, m)  # EF21/async server view
        if faults.async_on(cfg):
            st |= staleness.init_tree(params, m)
        return st

    return FedOpt(
        name="agpdmm",
        init=init,
        round=partial(_round, cfg),
        server_params=lambda s: s["x_s"],
    )
