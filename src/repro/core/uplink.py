"""Uplink admission: what of a client's computed uplink reaches the server.

Every centralised round body (GPDMM/AGPDMM, SCAFFOLD and FedAvg, over the
masked arena, the stacked pytree, the sampled cohort and the host popstore)
hands its computed uplink to ``admit`` and keeps only its own server
update.  ``admit`` runs, in order:

    EF21 compression against the cached rows     (cfg.uplink_bits)
    the round's fault plan, restricted to a cohort's ``idx``
    wire injection                                -> ``sent``
    the participation mask (full population only)
    the screen, its median over the round's TRANSMITTERS
    the combined mask
    the staleness engine, or the select against the fallback -> ``admitted``
    the fault counters

The screen's median is taken over the rows that transmit this round
(participating and not silent), so the masked population and the cohort
that gathers the same rows demote the same clients: a computed-then-
discarded row never moves the median.

With no participation draw, no fault schedule, no screen and no EF21 the
admission adds no operation: ``admitted`` is the uplink itself and the
mask is None.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FederatedConfig
from repro.core import faults, staleness
from repro.core import tree_util as T
from repro.kernels import ops


class Admission(NamedTuple):
    sent: Any  # the transmitted uplink: EF21-integrated and wire-injected
    admitted: Any  # what enters the server mean (and the u_hat cache)
    mask: Optional[jax.Array]  # effective active mask; None = every row
    state: dict  # stale-slot updates of the bounded-staleness engine
    metrics: dict  # fault and stale counters


def participation_key(cfg: FederatedConfig, round_idx):
    """The round's participation RNG key: folded from ``cfg.seed``, so every
    algorithm under comparison draws the SAME mask sequence by contract (the
    old hard-coded key(17) made that an accident of duplication)."""
    return jax.random.fold_in(jax.random.key(cfg.seed), round_idx)


def admit(cfg: FederatedConfig, uplink, *, arena, round_idx, m: int, ref,
          fallback, idx=None, state=None) -> Admission:
    """Admit one round's uplink.

    ``uplink``: the computed uplink rows, an ``(rows, width)`` arena buffer
    or a stacked client pytree.  ``arena``: the caller's layout -- its
    ``ArenaSpec`` for an arena buffer, None for a stacked pytree (a
    bare-array pytree looks like an arena buffer, and the two screens
    round differently).  ``round_idx``/``m``: the round counter and the
    population size the fault plan and the participation draw are made
    for.  ``ref``: the downlink reference the screen measures deviation
    from (the server row, or the server pytree).  ``fallback``: what a
    silent or demoted row sends in its place -- the cached ``u_hat`` rows
    (also EF21's integrator), or the server row for SCAFFOLD's zero delta;
    None where no row can fall silent.  ``idx``: a cohort's row ids (the
    cohort is the round's participants); None runs the full population
    under the participation mask.  ``state``: the round state, read by the
    staleness engine (full population only)."""
    if cfg.uplink_bits is not None:
        uplink = (ops.ef21_update(uplink, fallback, cfg.uplink_bits,
                                  arena.leaf_rows()) if arena is not None
                  else T.tree_quantize_delta(uplink, fallback, cfg.uplink_bits))
    plan = faults.plan(cfg, round_idx, m)
    pmask = None
    if idx is None:
        plan_r = plan
        if cfg.participation < 1.0:
            pmask = T.participation_mask(participation_key(cfg, round_idx), m,
                                         cfg.participation)
    else:
        plan_r = faults.take(plan, idx)
    # the wire corrupts what was TRANSMITTED, i.e. the EF21-integrated view
    inject = faults.inject if arena is not None else faults.inject_tree
    sent = inject(cfg.faults, plan_r, uplink)
    tx = faults.combine_mask(pmask, plan_r, None)  # the round's transmitters
    keep = None
    if faults.screening_on(cfg):
        screen = faults.screen_keep if arena is not None else faults.screen_keep_tree
        keep = screen(cfg, sent, ref, among=tx)
    mask = faults.combine_mask(tx, None, keep)
    admitted, stale, sm = sent, {}, {}
    if faults.async_on(cfg):
        # bounded-staleness engine: delayed rows buffer, arrivals mix into
        # the fallback; the returned mask also excludes delayed clients
        step = staleness.step_arena if arena is not None else staleness.step_tree
        admitted, mask, stale, sm = step(cfg, plan_r, sent, fallback, mask, state)
        tx = staleness.fresh_mask(tx, plan_r)  # delayed clients send nothing fresh
    elif mask is not None:
        # silent and demoted rows transmit nothing: the fallback stands in
        admitted = (jnp.where(mask[:, None], sent, fallback) if arena is not None
                    else T.tree_select(mask, sent, fallback))
    metrics = {}
    if plan is not None or keep is not None:
        metrics = faults.fault_metrics(plan, tx, keep) | sm
    return Admission(sent, admitted, mask, stale, metrics)
