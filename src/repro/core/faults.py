"""Deterministic fault injection + screening-to-silence (the robustness
layer shared by every round engine, ISSUE 6).

The design maps client faults onto the silence contract the round engines
already implement for partial participation (PR 5 pinned the
``(sum_active uplink + sum_silent u_hat) / m`` identity bit-identically):

* A **silent** client (dropout / straggler -- it never returns this round)
  simply contributes its cached ``u_hat`` row, exactly as a
  participation-masked client.  Stochastic/asynchronous PDMM with randomly
  inactive nodes converges (Sherson et al., arXiv:1706.02654; Zhang &
  Heusdens, arXiv:1702.00841), so silence is the one graceful degradation
  with theory attached.
* A **delayed** client (the soft class, ISSUE 7) finished its inner steps
  but its uplink is in flight for a drawn lateness of ``s`` rounds.  With
  the bounded-staleness engine on (``async_on``) the round routes the row
  through the stale buffer (``core.staleness``): it is stored this round,
  arrives ``s`` rounds later, and is admitted into the server mean with
  weight ``stale_gamma**s`` iff ``s <= max_staleness`` -- the stale-update
  regime of the same asynchronous-PDMM theory.  A lateness beyond
  ``deadline`` is demoted to plain silence AT PLAN TIME.  With the engine
  off (the default, and always on non-star topologies) ``delay`` IS a
  silence class, bit-identical to the pre-async behaviour.
* A **corrupt** client transmits, but the wire mangles the packet (NaN row,
  Inf row, sign flip, or a ``blowup``-scaled magnitude).  Uplink screening
  (``ops.screen_uplink``) detects the row in one fused pass -- per-client
  finite flags plus the squared deviation from the downlink reference
  (deviation, not plain norm: a sign-flipped uplink is norm-INVARIANT, but
  its deviation from x_s is ~ ||2 x_s||) -- and the server DEMOTES it to
  silent for the round.  Demotion means silent, full stop: the carry keeps
  its previous row, the cache keeps its previous uplink, the mean uses
  ``u_hat``.  A screened round is therefore bit-identical to a
  participation-masked round with the same effective mask
  (tests/test_faults.py pins this across all four algorithms).

Every draw is a pure function of ``(FaultConfig.seed, round, client)`` --
the round counter is folded into the seed -- so a fault trace replays
exactly across reruns, ``--resume``, and watchdog rollbacks
(``launch/train.py``).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FaultConfig, FederatedConfig
from repro.kernels import ops

# corruption classes, indexed by FaultPlan.kind
KINDS = ("nan", "inf", "sign", "blowup")
# fault-RNG fold ids are POSITIONAL in this tuple (0=dropout, 1=straggler,
# 2=delay); corrupt folds at 3, kind at 4, lateness at 5.  The delay draw
# keeps fold id 2 whether it lands in `silent` (engine off) or `delayed`
# (engine on), so silent|delayed is the same client set either way and the
# synchronous collapse is bitwise.
_SILENCE_CLASSES = ("dropout", "straggler", "delay")


class FaultPlan(NamedTuple):
    """The round's fault draw over the client population.

    silent:   (m,) bool -- client never returns this round (hard silence)
    corrupt:  (m,) bool -- client transmits a wire-mangled uplink (disjoint
              from silent and delayed: a client that does not return this
              round transmits nothing to corrupt)
    kind:     (m,) int32 -- corruption class index into ``KINDS``
    delayed:  (m,) bool -- uplink in flight through the stale buffer
              (all-False unless ``async_on``; disjoint from silent)
    lateness: (m,) int32 -- drawn rounds-late of each delayed client
              (0 on non-delayed rows)
    """

    silent: jax.Array
    corrupt: jax.Array
    kind: jax.Array
    delayed: jax.Array
    lateness: jax.Array


def async_on(cfg: FederatedConfig) -> bool:
    """Static policy: does this config run the bounded-staleness engine?

    Requires a ``delay`` schedule on the centralised star topology (graph
    rounds keep the silence contract -- there is no per-edge stale buffer).
    ``async_rounds="auto"`` engages exactly when the knobs deviate from the
    synchronous point (``max_staleness > 0`` or a finite ``deadline``);
    True forces the engine, False keeps delay = silence."""
    fc = cfg.faults
    if fc is None or fc.delay <= 0.0 or cfg.topology != "star":
        return False
    if cfg.async_rounds == "auto":
        return cfg.max_staleness > 0 or math.isfinite(cfg.deadline)
    return bool(cfg.async_rounds)


def fault_key(fc: FaultConfig, round_idx) -> jax.Array:
    """Fault RNG for one round: the round counter folded into the fault seed
    (independent of the data and participation seeds)."""
    return jax.random.fold_in(jax.random.key(fc.seed), round_idx)


def plan(cfg: FederatedConfig, round_idx, m: int) -> Optional[FaultPlan]:
    """Draw the round's fault plan; None when no fault schedule is active.

    Pure in ``(cfg.faults.seed, round_idx, client)``: calling it twice -- or
    from a replayed round after a rollback -- yields the identical plan.
    """
    fc = cfg.faults
    if fc is None or not fc.any:
        return None
    key = fault_key(fc, round_idx)

    def draw(cls_id: int, rate: float) -> jax.Array:
        if rate <= 0.0:
            return jnp.zeros((m,), bool)
        if rate >= 1.0:
            return jnp.ones((m,), bool)
        return jax.random.bernoulli(
            jax.random.fold_in(key, cls_id), rate, (m,))

    a_on = async_on(cfg)
    silent = jnp.zeros((m,), bool)
    delayed = jnp.zeros((m,), bool)
    for cls_id, name in enumerate(_SILENCE_CLASSES):
        hit = draw(cls_id, getattr(fc, name))
        if name == "delay" and a_on:
            # same fold id whether delay means silence or staleness, so
            # silent|delayed is the identical client set either way
            delayed = hit & ~silent
        else:
            silent = silent | hit
    lateness = jnp.zeros((m,), jnp.int32)
    if a_on:
        lateness = jax.random.randint(
            jax.random.fold_in(key, 5), (m,), 1, fc.delay_max + 1, jnp.int32)
        lateness = jnp.where(delayed, lateness, 0)
        if math.isfinite(cfg.deadline):
            # past the deadline -> demoted to the silence contract at plan
            # time: the uplink never enters the stale buffer
            late = delayed & (lateness.astype(jnp.float32)
                              > jnp.float32(cfg.deadline))
            silent = silent | late
            delayed = delayed & ~late
            lateness = jnp.where(delayed, lateness, 0)
    corrupt = draw(3, fc.corrupt) & ~silent & ~delayed
    kind = jax.random.randint(
        jax.random.fold_in(key, 4), (m,), 0, len(KINDS), jnp.int32)
    return FaultPlan(silent=silent, corrupt=corrupt, kind=kind,
                     delayed=delayed, lateness=lateness)


def take(plan_: Optional[FaultPlan], idx) -> Optional[FaultPlan]:
    """Restrict a population plan to a row subset (cohort indices / the
    static data-node list of a graph phase)."""
    if plan_ is None:
        return None
    idx = jnp.asarray(idx)
    return FaultPlan(silent=plan_.silent[idx], corrupt=plan_.corrupt[idx],
                     kind=plan_.kind[idx], delayed=plan_.delayed[idx],
                     lateness=plan_.lateness[idx])


def inject(fc: Optional[FaultConfig], plan_: Optional[FaultPlan], uplink):
    """Apply wire corruption to the transmitted (rows, width) uplink buffer.

    Corrupt rows become, by drawn class: all-NaN, all-Inf, sign-flipped, or
    ``blowup`` x the honest row.  No-op without a plan or a corrupt rate.
    """
    if plan_ is None or fc is None or fc.corrupt <= 0.0:
        return uplink
    u = uplink.astype(jnp.float32)
    k = plan_.kind[:, None]
    bad = jnp.where(
        k == 0, jnp.nan,
        jnp.where(k == 1, jnp.inf,
                  jnp.where(k == 2, -u, jnp.float32(fc.blowup) * u)))
    return jnp.where(plan_.corrupt[:, None], bad, u).astype(uplink.dtype)


def inject_tree(fc: Optional[FaultConfig], plan_: Optional[FaultPlan], uplink):
    """``inject`` over a stacked client pytree (leading dim m on every leaf)."""
    if plan_ is None or fc is None or fc.corrupt <= 0.0:
        return uplink

    def one(u):
        shape = (-1,) + (1,) * (u.ndim - 1)
        uf = u.astype(jnp.float32)
        k = plan_.kind.reshape(shape)
        bad = jnp.where(
            k == 0, jnp.nan,
            jnp.where(k == 1, jnp.inf,
                      jnp.where(k == 2, -uf, jnp.float32(fc.blowup) * uf)))
        return jnp.where(plan_.corrupt.reshape(shape), bad, uf).astype(u.dtype)

    return jax.tree.map(one, uplink)


def screening_on(cfg: FederatedConfig) -> bool:
    """"auto" screens exactly when a fault schedule is configured; True/False
    force it on/off."""
    if cfg.screen == "auto":
        return cfg.faults is not None and cfg.faults.any
    return bool(cfg.screen)


def needs_cache(cfg: FederatedConfig) -> bool:
    """Whether the server must hold the u_hat uplink cache for fault
    tolerance: any fault schedule (silent clients fall back to the cache) or
    any screening (demoted clients do)."""
    return screening_on(cfg) or (cfg.faults is not None and cfg.faults.any)


def _keep_from(cfg: FederatedConfig, finite, sq, among):
    keep = finite
    if cfg.screen_mult > 0.0:
        # median over the transmitting rows that screened finite; all-NaN
        # median (no such row at all) propagates NaN -> comparison False ->
        # every row demoted, consistently
        rows = finite if among is None else finite & among
        med = jnp.nanmedian(jnp.where(rows, sq, jnp.nan))
        keep = keep & (sq <= jnp.float32(cfg.screen_mult)
                       * jnp.maximum(med, jnp.float32(1e-12)))
    return keep


def screen_keep(cfg: FederatedConfig, uplink, ref, among=None):
    """Screen a (rows, width) uplink buffer against the downlink reference
    ``ref`` ((width,) broadcast row, or (rows, width) per-row).  Returns the
    (rows,) bool KEEP mask: finite and not a norm outlier
    (> screen_mult x the median squared deviation over the rows in
    ``among``, the round's transmitters; None = every row)."""
    finite, sq = ops.screen_uplink(uplink, ref)
    return _keep_from(cfg, finite, sq, among)


def screen_keep_tree(cfg: FederatedConfig, uplink, ref_tree, among=None):
    """``screen_keep`` over a stacked client pytree vs the server pytree:
    the flags/deviations reduce over ALL leaves, so the rule matches the
    packed-arena screen on the same state."""
    leaves_u = jax.tree.leaves(uplink)
    leaves_r = jax.tree.leaves(ref_tree)
    m = leaves_u[0].shape[0]
    finite = jnp.ones((m,), bool)
    sq = jnp.zeros((m,), jnp.float32)
    for u, r in zip(leaves_u, leaves_r):
        uf = u.astype(jnp.float32).reshape(m, -1)
        rf = r.astype(jnp.float32).reshape(1, -1)
        fin_e = jnp.isfinite(uf)
        d = jnp.where(fin_e, uf - rf, 0.0)
        finite = finite & jnp.all(fin_e, axis=1)
        sq = sq + jnp.sum(d * d, axis=1)
    return _keep_from(cfg, finite, sq, among)


def combine_mask(mask, plan_: Optional[FaultPlan], keep):
    """AND the participation mask, the plan's silence, and the screening keep
    mask into the round's effective active mask (None = everyone active)."""
    out = mask
    if plan_ is not None:
        alive = ~plan_.silent
        out = alive if out is None else out & alive
    if keep is not None:
        out = keep if out is None else out & keep
    return out


def fault_metrics(plan_: Optional[FaultPlan], transmitters, keep) -> dict:
    """Round fault counters (f32 scalars, scan-stackable):

    ``faults_injected`` -- clients hit by the schedule this round (silent,
    corrupt, or delayed, over the population the plan was drawn for);
    ``faults_demoted`` -- transmitting clients the screen silenced.
    """
    f32 = jnp.float32
    injected = (jnp.zeros((), f32) if plan_ is None
                else jnp.sum((plan_.silent | plan_.corrupt
                              | plan_.delayed).astype(f32)))
    if keep is None:
        demoted = jnp.zeros((), f32)
    else:
        t = jnp.ones_like(keep) if transmitters is None else transmitters
        demoted = jnp.sum((t & ~keep).astype(f32))
    return {"faults_injected": injected, "faults_demoted": demoted}
