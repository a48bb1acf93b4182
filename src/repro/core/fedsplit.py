"""FedSplit (Pathak & Wainwright 2020), eqs. (16)-(17), and Inexact FedSplit,
eqs. (18)-(19) -- including the IMPROPER client initialisation
x_i^{r,0} = z_{s|i}^r whose failure the paper diagnoses (Fig. 1).

Exact iterates (prox oracle):
    x_i^{r+1}    = prox_{gamma f_i}(z_{s|i}^r)
                 = argmin_x f_i(x) + 1/(2 gamma) ||x - z_{s|i}^r||^2
    z_{i|s}^{r+1} = 2 x_i^{r+1} - z_{s|i}^r
    x_s^{r+1}    = mean_i z_{i|s}^{r+1}
    z_{s|i}^{r+1} = 2 x_s^{r+1} - z_{i|s}^{r+1}

Inexact iterates: K gradient steps on h_i^r(x) = f_i(x) + ||x - z||^2/(2 gamma)
starting (faithfully, badly) from z_{s|i}^r; ``fedsplit_init="xs"`` switches to
the x_s^r init, under which the method converges (Fig. 1's dashed curves).

PDMM == FedSplit on the star graph (paper SSIII-B): with rho = 1/gamma and
z_{s|i} = x_s - gamma lam_{s|i}, the exact iterates coincide with
``core.pdmm``; ``tests/test_core.py`` asserts the trajectory identity.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import FederatedConfig
from repro.core import arena
from repro.core import tree_util as T
from repro.core.api import (
    FedOpt, arena_grad, map_clients, resolved_rho, use_arena,
)
from repro.kernels import ops


def _gamma(cfg: FederatedConfig) -> float:
    return cfg.gamma if cfg.gamma is not None else 1.0 / resolved_rho(cfg)


# ---------------------------------------------------------------------------
# exact (prox oracle)
# ---------------------------------------------------------------------------

def _round_exact(cfg: FederatedConfig, state, prox_fn, batch=None, per_step_batches=False):
    """prox_fn(z_i, rho) vmapped over clients: argmin f_i + rho/2 ||x - z||^2."""
    del batch, per_step_batches
    gamma = _gamma(cfg)
    z_s = state["z_s"]  # stacked (m, ...)
    x_i = prox_fn(z_s, 1.0 / gamma)  # prox_fn maps the stacked client dim itself
    z_is = T.tmap(lambda x, z: 2.0 * x - z, x_i, z_s)
    x_s = T.tree_client_mean(z_is)
    m = jax.tree.leaves(z_s)[0].shape[0]
    z_s_new = T.tmap(lambda s, z: 2.0 * s - z, T.tree_broadcast(x_s, m), z_is)
    new_state = {"x_s": x_s, "z_s": z_s_new, "round": state["round"] + 1}
    return new_state, {}


def make_exact(cfg: FederatedConfig) -> FedOpt:
    def init(params, m):
        return {
            "x_s": params,
            "z_s": T.tree_broadcast(params, m),  # z_{s|i}^0 = x_s^0 (lam = 0)
            "round": jnp.zeros((), jnp.int32),
        }

    return FedOpt(
        name="fedsplit_exact",
        init=init,
        round=partial(_round_exact, cfg),
        server_params=lambda s: s["x_s"],
    )


# ---------------------------------------------------------------------------
# inexact (K gradient steps, paper eq. (18))
# ---------------------------------------------------------------------------

def _round_inexact_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """Inexact FedSplit over the flat arena: the K gradient steps and the
    reflect/average/reflect tail run on one (m, width) buffer per state
    tensor instead of per-leaf tree.map chains.  The gradient resolves via
    the ``core.api`` oracle protocol -- arena-native oracles evaluate on the
    packed buffer directly (0 boundary passes per step)."""
    gamma = _gamma(cfg)
    K, eta = cfg.inner_steps, cfg.eta
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    z = state["z_s"]  # arena-resident (m, width)
    m = z.shape[0]
    x_s_row = spec.pack(state["x_s"])
    grad_a, _native = arena_grad(grad_fn, spec)

    if cfg.fedsplit_init == "z":
        x0 = z  # the paper's diagnosed improper init
    elif cfg.fedsplit_init == "xs":
        x0 = jnp.broadcast_to(x_s_row[None], z.shape)
    else:
        raise ValueError(cfg.fedsplit_init)

    def one_step(x, xs_k):
        b = xs_k if per_step_batches else batch
        g = grad_a(x, b)
        # grad h = grad f + (x - z)/gamma: lam-free fused step, rho = 1/gamma
        return ops.fused_update(x, g, z, None, eta, 1.0 / gamma), None

    if per_step_batches:
        x_K, _ = jax.lax.scan(one_step, x0, batch)
    else:
        x_K, _ = jax.lax.scan(one_step, x0, None, length=K)

    z_is = 2.0 * x_K - z
    x_s_new = jnp.mean(z_is, axis=0)
    z_s_new = 2.0 * x_s_new[None] - z_is
    new_state = {
        "x_s": spec.unpack(x_s_new),
        "z_s": z_s_new,
        "round": state["round"] + 1,
    }
    drift = jnp.sum(jnp.square((x_K - x_s_row[None]).astype(jnp.float32)), axis=1)
    return new_state, {"client_drift": jnp.mean(drift),
                       "used_arena": jnp.ones((), jnp.float32)}


def _round_inexact(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False):
    if use_arena(cfg, state["x_s"]):
        return _round_inexact_arena(cfg, state, grad_fn, batch, per_step_batches)
    gamma = _gamma(cfg)
    K, eta = cfg.inner_steps, cfg.eta
    z_s, x_s = state["z_s"], state["x_s"]
    m = jax.tree.leaves(z_s)[0].shape[0]
    vgrad = partial(map_clients, grad_fn)

    if cfg.fedsplit_init == "z":
        x0 = z_s  # the paper's diagnosed improper init
    elif cfg.fedsplit_init == "xs":
        x0 = T.tree_broadcast(x_s, m)
    else:
        raise ValueError(cfg.fedsplit_init)

    def one_step(x, xs_k):
        b = xs_k if per_step_batches else batch
        g = vgrad(x, b)
        # grad h = grad f + (x - z)/gamma: lam-free fused step, rho = 1/gamma
        x_new = T.tmap(
            lambda xx, gg, zz: ops.fused_update(xx, gg, zz, None, eta, 1.0 / gamma),
            x, g, z_s,
        )
        return x_new, None

    if per_step_batches:
        x_K, _ = jax.lax.scan(one_step, x0, batch)
    else:
        x_K, _ = jax.lax.scan(one_step, x0, None, length=K)

    z_is = T.tmap(lambda x, z: 2.0 * x - z, x_K, z_s)
    x_s_new = T.tree_client_mean(z_is)
    z_s_new = T.tmap(lambda s, z: 2.0 * s - z, T.tree_broadcast(x_s_new, m), z_is)
    new_state = {"x_s": x_s_new, "z_s": z_s_new, "round": state["round"] + 1}
    metrics = {
        "client_drift": jnp.mean(T.tree_client_sqnorms(T.tree_sub(x_K, T.tree_broadcast(x_s, m)))),
        "used_arena": jnp.zeros((), jnp.float32),
    }
    return new_state, metrics


def make_inexact(cfg: FederatedConfig) -> FedOpt:
    def init(params, m):
        if use_arena(cfg, params):
            spec = arena.ArenaSpec.from_tree(params)
            row = spec.pack(params)
            return {
                "x_s": params,
                "z_s": jnp.broadcast_to(row[None], (m, spec.width)),
                "round": jnp.zeros((), jnp.int32),
            }
        return {
            "x_s": params,
            "z_s": T.tree_broadcast(params, m),
            "round": jnp.zeros((), jnp.int32),
        }

    return FedOpt(
        name=f"fedsplit_inexact[{cfg.fedsplit_init}]",
        init=init,
        round=partial(_round_inexact, cfg),
        server_params=lambda s: s["x_s"],
    )
