"""GPDMM (Algorithm 1, Zhang et al. 2021): gradient-based PDMM for a
centralised network, one transmitted variable per direction per round.

Per round r (client i, K inner steps, rho = 1/(K eta) by default):

    x_i^{r,0}   = x_i^{r-1,K}                        (carry, NOT x_s - lam/rho:
                                                      the Inexact-FedSplit fix)
    x_i^{r,k+1} = x_i^{r,k} - (1/(1/eta+rho)) [grad f_i(x_i^{r,k})
                                               + rho (x_i^{r,k} - x_s^r)
                                               + lam_{s|i}^r]        (eq. 20)
    lam_{i|s}^{r+1} = rho (x_s^r - xref_i) - lam_{s|i}^r             (eq. 23/24)
    uplink   u_i   = xref_i - lam_{i|s}^{r+1} / rho                 (ONE var)
    x_s^{r+1}      = mean_i u_i                                      (all-reduce)
    lam_{s|i}^{r+1} = rho (xref_i - x_s^{r+1}) - lam_{i|s}^{r+1}     (local)

where xref_i = mean_k x_i^{r,k} (eq. 23, Alg. 1) or x_i^{r,K} (eq. 24,
Remark 1) when ``use_avg=False``.

Communication note (recorded in EXPERIMENTS.md): in the SPMD mapping the
uplink-mean is one all-reduce of a single parameter-sized tensor; the downlink
combination x_s - lam_{s|i}/rho is reconstructed client-locally, so GPDMM's
1-variable-per-direction claim is exactly one collective per round.
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
    FedOpt, affine_case, arena_grad, cohort_batch, map_clients, resolved_rho,
    run_cohort_inner, step_by_client, use_arena, use_cohort,
)
from repro.kernels import ops


def _eta_val(eta):
    """Kernel-ready view of ``cfg.eta``: the host-resolved per-client tuple
    (``eta="auto"``, see ``core.autotune.resolve``) becomes a static
    ``(m,) np.float32`` array; scalars (and already-traced per-cohort rows)
    pass through untouched, so the scalar path's step arithmetic stays the
    identical baked Python float and its traced graphs are bitwise
    unchanged."""
    return np.asarray(eta, np.float32) if isinstance(eta, tuple) else eta


def _step_for(step, leaf):
    """Per-leaf view of a (possibly per-client) stepsize for the pytree
    path: scalars pass through, per-client arrays broadcast over the leaf's
    trailing dims."""
    if np.ndim(step) == 0:
        return step
    return jnp.asarray(step, jnp.float32).reshape((-1,) + (1,) * (leaf.ndim - 1))


def inner_steps(grad_fn, x0, x_s_b, lam_s, batch, *, K, eta, rho, per_step,
                vr_snapshot=None):
    """Runs the K inexact-PDMM client steps (shared by GPDMM/AGPDMM).

    x0, x_s_b, lam_s: stacked (m, ...) pytrees.  Returns (x_K, x_bar).

    ``vr_snapshot`` (beyond paper; requires ``per_step`` batches): SVRG-style
    variance reduction in the stochastic setting the paper names as future
    work (SSVII), following [14]'s PDMM+SVRG for P2P networks.  With snapshot
    z (the round's fresh server estimate) the step-k gradient becomes

        g_k(x) - g_k(z) + mean_j g_j(z)

    -- unbiased, with variance -> 0 as x -> z, restoring the deterministic
    rates under minibatch noise at the cost of 2x gradient evals per step
    plus one pass at the snapshot.
    """
    eta = _eta_val(eta)
    step_c = 1.0 / (1.0 / eta + rho)
    vgrad = partial(map_clients, grad_fn)

    gbar = None
    if vr_snapshot is not None:
        assert per_step, "SVRG needs per-step minibatches (K, m, ...)"
        # full-pass gradient at the snapshot: mean over the K step batches
        snap_grads = jax.lax.map(lambda b: vgrad(vr_snapshot, b), batch)
        gbar = T.tmap(lambda t: jnp.mean(t, axis=0), snap_grads)

    def one_step(carry, xs_k):
        x, xsum = carry
        b = xs_k if per_step else batch
        g = vgrad(x, b)
        if gbar is not None:
            g_snap = vgrad(vr_snapshot, b)
            g = T.tmap(lambda a, c, d: a - c + d, g, g_snap, gbar)
        x_new = T.tmap(
            lambda xx, gg, ss, ll: ops.fused_update(
                xx, gg, ss, ll, _step_for(step_c, xx), rho),
            x, g, x_s_b, lam_s,
        )
        return (x_new, T.tree_add(xsum, x_new)), None

    init = (x0, T.tree_zeros_like(x0))
    if per_step:
        (x_K, xsum), _ = jax.lax.scan(one_step, init, batch)
    else:
        (x_K, xsum), _ = jax.lax.scan(one_step, init, None, length=K)
    return x_K, T.tree_scale(xsum, 1.0 / K)


def inner_steps_arena(spec, grad_fn, x0, x_s_row, lam, batch, *, K, eta, rho,
                      per_step, vr_snapshot=None, average=True):
    """Arena counterpart of ``inner_steps``: client state carried as one
    ``(m, width)`` buffer, end to end.  Returns ``(x_K, x_sum)``: x_sum is
    what x_bar is made from, with the factor ``avg_scale`` gives (the
    running sum of the K iterates, scaled by 1/K inside the uplink kernel),
    and None with ``average=False``, where no sum is formed.

    Gradient oracle resolution (``core.api`` protocol), fastest first:

      1. ``grad_fn.affine_arena`` + the width fits VMEM (and the plain
         full-batch case): the WHOLE K-step loop is ONE fused kernel
         (``kernels/inner_loop.py``) -- 1 HBM read + 1 write of the client
         state for the entire inner loop.  It returns x_bar itself.
      2. ``grad_fn.grad_arena``: one fused-update kernel per step with the
         gradient evaluated directly on the packed buffer -- 0 boundary
         passes.  The kernel carries the running sum beside x
         (``ops.fused_update_arena_sum``): the first step, peeled out of the
         scan, writes x_1 as the sum, and each later step adds to it in
         place.
      3. plain ``grad_fn``: same scan, paying the unpack->grad->pack round
         trip through the model's pytree each step, one client at a time
         (``step_by_client``): each client's row is stepped as soon as its
         gradient is made (``ops.fused_update_client``), so no ``(m,
         width)`` gradient is stacked.  The sum is an add after each step:
         peeling the first step would trace the model's gradient twice.

    ``eta`` may be a scalar, the per-client tuple (auto-eta), or an
    already-gathered per-cohort row -- array forms ride the kernels as a
    per-client stepsize operand (``kernels/ops``).
    """
    eta = _eta_val(eta)
    step_c = 1.0 / (1.0 / eta + rho)
    with jax.named_scope("round.inner_loop"):
        affine = affine_case(grad_fn, spec, per_step=per_step,
                             svrg=vr_snapshot is not None)
        if affine is not None:
            H, c = affine(spec, batch)
            x_K, x_bar = ops.inner_loop_affine(x0, H, c, x_s_row, lam, step_c, rho, K)
            return x_K, x_bar if average else None

        grad_a, native = arena_grad(grad_fn, spec)

        vr = None
        if vr_snapshot is not None:
            assert per_step, "SVRG needs per-step minibatches (K, m, ...)"
            with jax.named_scope("round.client_grad"):
                snap_grads = jax.lax.map(lambda b: grad_a(vr_snapshot, b), batch)
                vr = (vr_snapshot, jnp.mean(snap_grads, axis=0))
        # a per-client stepsize rides with the duals as a client row
        step_rows = () if np.ndim(step_c) == 0 else (jnp.asarray(step_c, jnp.float32),)

        def batch_at(k):
            if not per_step:
                return batch
            return jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(t, k, keepdims=False), batch)

        def scan_steps(step, carry, first=0):
            """``carry = step(carry, batch_at(k))`` for inner steps k =
            first..K-1."""
            return jax.lax.scan(lambda c, k: (step(c, batch_at(k)), None),
                                carry, jnp.arange(first, K))[0]

        if native:
            def grad(x, b):
                with jax.named_scope("round.client_grad"):
                    g = grad_a(x, b)
                    if vr is not None:
                        g = g - grad_a(vr[0], b) + vr[1]
                return g

            def plain(x, b):
                with jax.named_scope("round.client_update"):
                    return ops.fused_update_arena(x, grad(x, b), x_s_row, lam,
                                                  step_c, rho)

            if not average or K == 1:
                x_K = scan_steps(plain, x0)
                return x_K, x_K if average else None

            def summing(carry, b):
                x, xsum = carry
                g = grad(x, b)
                with jax.named_scope("round.client_update"):
                    return ops.fused_update_arena_sum(x, g, x_s_row, lam, xsum,
                                                      step_c, rho)

            return scan_steps(summing, summing((x0, None), batch_at(0)), first=1)

        def update(x, g, i, lam, *rest):
            *st, x_s_row = rest
            return ops.fused_update_client(x, g, x_s_row, lam, i,
                                           st[0] if st else step_c, rho)

        def by_client(x, b):
            return step_by_client(spec, grad_fn, update, x, b,
                                  (lam, *step_rows), (x_s_row,), vr=vr)

        if not average:
            return scan_steps(by_client, x0), None

        def by_client_sum(carry, b):
            x_new = by_client(carry[0], b)
            return x_new, carry[1] + x_new

        return scan_steps(by_client_sum, (x0, jnp.zeros_like(x0)))


def avg_scale(spec, grad_fn, K, *, per_step, svrg=False):
    """The factor that makes x_bar (eq. 23) of ``inner_steps_arena``'s
    second output, handed to ``ops.round_tail``: 1/K on the running sum of
    the iterates, None where the affine K-step kernel returns x_bar itself."""
    affine = affine_case(grad_fn, spec, per_step=per_step, svrg=svrg)
    return None if affine is not None else 1.0 / K


def uplink_ref(cfg: FederatedConfig, spec, grad_fn, per_step, x_K, x_sum):
    """``(x_ref, scale)`` for ``ops.round_tail``: the average of the inner
    iterates (eq. 23) as ``inner_steps_arena``'s sum and its factor, or x_K
    (eq. 24, ``use_avg=False``) as it is."""
    if not cfg.use_avg:
        return x_K, None
    return x_sum, avg_scale(spec, grad_fn, cfg.inner_steps, per_step=per_step,
                            svrg=cfg.variance_reduction == "svrg")


def participation_key(cfg: FederatedConfig, round_idx):
    """The round's participation RNG key: folded from ``cfg.seed``, so every
    algorithm under comparison draws the SAME mask sequence by contract (the
    old hard-coded key(17) made that an accident of duplication)."""
    return jax.random.fold_in(jax.random.key(cfg.seed), round_idx)


def arena_tail(cfg: FederatedConfig, spec, state, uplink, m):
    """Shared GPDMM/AGPDMM arena round tail: fused EF21 quantise-delta,
    fault injection + uplink screening (core.faults), the combined
    participation/fault/screen select vs the u_hat cache, the single
    client-mean all-reduce, and the fused dual refresh.  Returns
    (state_updates, x_s_new_row, lam_s_new, mask, fault_metrics) -- ``mask``
    is the round's effective active mask (None = every uplink entered the
    mean); demoted and faulted clients are SILENT, full stop, so the round
    is bit-identical to a participation-masked round with the same mask.

    With the bounded-staleness engine on (``faults.async_on``) the select
    against the cache routes through ``staleness.step_arena`` instead:
    delayed rows are buffered, arriving stale rows mix into the cache with
    their discounted weight, and the returned mask additionally excludes
    delayed clients (their carry keeps, like a silent client's)."""
    rho = resolved_rho(cfg)
    new_state = {}
    u_hat = state.get("u_hat")  # arena-resident (m, width) or absent
    if cfg.uplink_bits is not None:  # fused EF21: 2 passes instead of ~4
        uplink = ops.ef21_update(uplink, u_hat, cfg.uplink_bits, spec.leaf_rows())
    # the wire corrupts what was TRANSMITTED, i.e. the EF21-integrated view
    fplan = faults.plan(cfg, state["round"], m)
    uplink = faults.inject(cfg.faults, fplan, uplink)
    pmask = None
    if cfg.participation < 1.0:
        pmask = T.participation_mask(
            participation_key(cfg, state["round"]), m, cfg.participation
        )
    keep = None
    if faults.screening_on(cfg):
        keep = faults.screen_keep(cfg, uplink, spec.pack(state["x_s"]))
    mask = faults.combine_mask(pmask, fplan, keep)
    sm = {}
    if faults.async_on(cfg):
        uplink, mask, stale_up, sm = staleness.step_arena(
            cfg, fplan, uplink, u_hat, mask, state)
        new_state |= stale_up
    elif mask is not None:
        uplink = jnp.where(mask[:, None], uplink, u_hat)
    if u_hat is not None:
        new_state["u_hat"] = uplink
    with jax.named_scope("round.server_mean"):
        x_s_new = jnp.mean(uplink, axis=0)  # <- the round's single all-reduce
    # fused tail pass 2: lam' = rho (u - x_s'), server row broadcast in-kernel
    with jax.named_scope("round.dual_refresh"):
        lam_s_new = ops.dual_from_uplink(uplink, x_s_new, rho)
    fm = {}
    if fplan is not None or keep is not None:
        tx = faults.combine_mask(pmask, fplan, None)
        if faults.async_on(cfg):
            # delayed clients transmit nothing fresh this round
            tx = staleness.fresh_mask(tx, fplan)
        fm = faults.fault_metrics(fplan, tx, keep) | sm
    return new_state, x_s_new, lam_s_new, mask, fm


def arena_metrics(lam_s_new, x_K, x_s_row, mask=None):
    """KKT-invariant and drift metrics straight off the arena buffers;
    padding columns are identically zero, so no masking is needed there.
    ``client_drift`` averages over the ACTIVE cohort only (``mask``, or all
    rows of ``x_K`` when None -- the cohort path passes its already-gathered
    x_K): silent clients' x_K is computed-then-discarded on the masked path
    (the carry is kept), so averaging it in reported movement that never
    entered the state.  ``used_arena`` records the (static) layout decision
    so benches can see which path a round actually ran."""
    f32 = jnp.float32
    with jax.named_scope("round.metrics"):
        return {
            "lam_sum_norm": jnp.linalg.norm(jnp.sum(lam_s_new.astype(f32), axis=0)),
            "client_drift": T.masked_client_mean(
                jnp.sum(jnp.square((x_K - x_s_row[None]).astype(f32)), axis=1), mask
            ),
            "used_arena": jnp.ones((), f32),
        }


def cohort_tail(cfg: FederatedConfig, spec, state, uplink, idx, fplan=None):
    """Shared GPDMM/AGPDMM cohort round tail (the cohort sibling of
    ``arena_tail``): fused EF21 against the cohort's cached ``u_hat`` rows,
    fault injection + screening on the cohort uplink, the scatter into the
    population cache, the scattered-mean server update (the
    ``(sum_active uplink + sum_silent u_hat) / m`` identity, computed as ONE
    mean over the scattered buffer so it matches the masked path bitwise),
    and the full dual refresh.  Returns ``({u_hat, x_s, lam_s}, keep_c,
    fault_metrics)`` -- ``keep_c`` is the cohort-shaped surviving mask (None
    = the whole cohort's uplink entered the cache).  Note the screening
    median is taken over the COHORT, not the population."""
    rho = resolved_rho(cfg)
    u_hat = state["u_hat"]  # guaranteed: participation < 1 carries the cache
    if cfg.uplink_bits is not None:  # EF21 on the cohort's cached rows only
        uplink = ops.ef21_update(uplink, ops.row_gather(u_hat, idx),
                                 cfg.uplink_bits, spec.leaf_rows())
    plan_c = faults.take(fplan, idx)
    uplink = faults.inject(cfg.faults, plan_c, uplink)
    keep = None
    if faults.screening_on(cfg):
        keep = faults.screen_keep(cfg, uplink, spec.pack(state["x_s"]))
    keep_c = faults.combine_mask(None, plan_c, keep)
    if keep_c is not None:
        uplink = jnp.where(keep_c[:, None], uplink, ops.row_gather(u_hat, idx))
    u_hat_new = ops.row_scatter(u_hat, idx, uplink)
    with jax.named_scope("round.server_mean"):
        x_s_new = jnp.mean(u_hat_new, axis=0)  # <- the round's single all-reduce
    with jax.named_scope("round.dual_refresh"):
        lam_s_new = ops.dual_from_uplink(u_hat_new, x_s_new, rho)
    fm = {}
    if fplan is not None or keep is not None:
        fm = faults.fault_metrics(
            fplan, None if plan_c is None else ~plan_c.silent, keep)
    return {
        "u_hat": u_hat_new,
        "x_s": spec.unpack(x_s_new),
        "lam_s": lam_s_new,
    }, keep_c, fm


def popstore_tail(cfg: FederatedConfig, spec, x_s_row, u_hat_c, uplink, idx,
                  round_idx, m):
    """Cohort-resident round tail for the HOST-popstore path (shared by
    GPDMM/AGPDMM/FedAvg): identical per-row math to ``cohort_tail`` --
    fused EF21 against the STAGED cohort ``u_hat`` rows (the host store's
    copy of exactly the rows ``cohort_tail`` would ``row_gather``), fault
    injection + screening on the cohort uplink, and the combined keep-select
    back to the staged rows.  What it does NOT do is the O(m) tail: no
    scatter into a device-resident population buffer, no full-buffer mean,
    no dense dual refresh -- the host driver (``core.popstore.Runner``)
    scatters the returned rows into the host store and maintains the server
    mean incrementally.  Returns ``(uplink, keep_c, fault_metrics)``."""
    if cfg.uplink_bits is not None:
        uplink = ops.ef21_update(uplink, u_hat_c, cfg.uplink_bits,
                                 spec.leaf_rows())
    fplan = faults.plan(cfg, round_idx, m)
    plan_c = faults.take(fplan, idx)
    uplink = faults.inject(cfg.faults, plan_c, uplink)
    keep = None
    if faults.screening_on(cfg):
        keep = faults.screen_keep(cfg, uplink, x_s_row)
    keep_c = faults.combine_mask(None, plan_c, keep)
    if keep_c is not None:
        # demoted/faulted cohort rows are silent: the store keeps their row
        uplink = jnp.where(keep_c[:, None], uplink, u_hat_c)
    fm = {}
    if fplan is not None or keep is not None:
        fm = faults.fault_metrics(
            fplan, None if plan_c is None else ~plan_c.silent, keep)
    return uplink, keep_c, fm


def popstore_body(cfg: FederatedConfig, spec, m: int, grad_fn, per_step):
    """Device half of a host-popstore GPDMM round (see ``core.popstore``).

    The returned ``body(server, staged, idx, round_idx, batch)`` touches
    ONLY O(cohort) device memory: ``staged`` carries the sampled rows of the
    host store (``u_hat`` -- the server's cached uplink view -- and ``x_c``,
    the primal carry), and the dual rows are reconstructed LAZILY via the
    round invariant lam_{s|i} = rho (u_hat_i - x_s) (``ops.dual_from_uplink``
    on the staged rows -- elementwise, so bit-identical to gathering rows of
    the dense refresh the arena path materialises).  Returns
    ``(rows_out, server_rows, metrics)`` where ``rows_out = {u_hat, x_c}``
    scatters back into the host store."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    f32 = jnp.float32

    eta_v = _eta_val(cfg.eta)
    per_client = np.ndim(eta_v) > 0

    def body(server, staged, idx, round_idx, batch):
        x_s_row = spec.pack(server["x_s"])
        u_hat_c, x0_c = staged["u_hat"], staged["x_c"]
        lam_c = ops.dual_from_uplink(u_hat_c, x_s_row, rho)  # lazy dual
        batch_c = cohort_batch(batch, idx, m, per_step)

        def inner(rows, b):
            x0, lam_t = rows[0], rows[1]
            # per-client eta rides the rows tuple so the cohort tiler slices
            # it alongside the state rows (a closure capture would stay
            # cohort-sized inside a tile-sized call)
            eta_t = rows[2] if per_client else eta_v
            snap = (jnp.broadcast_to(x_s_row[None], x0.shape)
                    if cfg.variance_reduction == "svrg" else None)
            return inner_steps_arena(
                spec, grad_fn, x0, x_s_row, lam_t, b, K=K, eta=eta_t,
                rho=rho, per_step=per_step, vr_snapshot=snap,
                average=cfg.use_avg,
            )

        rows = (x0_c, lam_c) + (
            (jnp.asarray(eta_v)[idx],) if per_client else ())
        x_K, x_sum = run_cohort_inner(cfg, inner, rows, batch_c,
                                      per_step=per_step)
        x_ref, scale = uplink_ref(cfg, spec, grad_fn, per_step, x_K, x_sum)
        _, uplink = ops.round_tail(x_ref, lam_c, x_s_row, rho,
                                   with_lam_is=False, scale=scale)
        uplink, keep_c, fm = popstore_tail(cfg, spec, x_s_row, u_hat_c,
                                           uplink, idx, round_idx, m)
        x_K_kept = (x_K if keep_c is None
                    else jnp.where(keep_c[:, None], x_K, x0_c))
        metrics = {
            "client_drift": T.masked_client_mean(
                jnp.sum(jnp.square((x_K - x_s_row[None]).astype(f32)),
                        axis=1), keep_c),
            "used_arena": jnp.ones((), f32),
        } | fm
        return {"u_hat": uplink, "x_c": x_K_kept}, {}, metrics

    return body


def _round_arena_cohort(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches):
    """GPDMM round over the SAMPLED COHORT (ISSUE 5): gather the round's
    active rows out of the population arena, run the fused inner loop +
    round tail on the ``(m_active, width)`` cohort buffer (tiled via
    ``cohort_tile`` when set), scatter the updated rows back.  Compute and
    gradient-batch traffic scale with the cohort, not the population; the
    O(m) work that remains is inherent to the algorithm (every client's
    lam_{s|i} moves with the new x_s, and the server mean reads every cached
    u_hat row).

    Row-for-row identical to the masked path: the cohort rows see the same
    per-row kernels, and the server mean is taken over the SCATTERED
    population buffer -- the same mean-of-selected-rows the masked path
    computes, realising (sum_active uplink + sum_silent u_hat) / m without a
    reordered reduction (tests/test_cohort.py pins this per round)."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam, x_c = state["lam_s"], state["x_c"]
    m = lam.shape[0]
    x_s_row = spec.pack(state["x_s"])
    idx, mask = T.cohort_indices(
        participation_key(cfg, state["round"]), m, cfg.participation
    )
    lam_c = ops.row_gather(lam, idx)
    x0_c = ops.row_gather(x_c, idx)
    batch_c = cohort_batch(batch, idx, m, per_step_batches)
    eta_v = _eta_val(cfg.eta)
    per_client = np.ndim(eta_v) > 0

    def inner(rows, b):
        x0, lam_t = rows[0], rows[1]
        eta_t = rows[2] if per_client else eta_v  # tiled with the state rows
        snap = (jnp.broadcast_to(x_s_row[None], x0.shape)
                if cfg.variance_reduction == "svrg" else None)
        return inner_steps_arena(
            spec, grad_fn, x0, x_s_row, lam_t, b, K=K, eta=eta_t, rho=rho,
            per_step=per_step_batches, vr_snapshot=snap, average=cfg.use_avg,
        )

    rows = (x0_c, lam_c) + ((jnp.asarray(eta_v)[idx],) if per_client else ())
    x_K, x_sum = run_cohort_inner(cfg, inner, rows, batch_c,
                                  per_step=per_step_batches)
    x_ref, scale = uplink_ref(cfg, spec, grad_fn, per_step_batches, x_K, x_sum)

    with jax.named_scope("round.uplink"):
        _, uplink = ops.round_tail(x_ref, lam_c, x_s_row, rho,
                                   with_lam_is=False, scale=scale)
    fplan = faults.plan(cfg, state["round"], m)
    new_state, keep_c, fm = cohort_tail(cfg, spec, state, uplink, idx, fplan)
    # demoted cohort rows are silent, full stop: the carry keeps its
    # round-start row exactly as a never-sampled client's does
    x_K_kept = x_K if keep_c is None else jnp.where(keep_c[:, None], x_K, x0_c)
    new_state |= {
        "x_c": ops.row_scatter(x_c, idx, x_K_kept),  # silent clients keep carry
        "round": state["round"] + 1,
    }
    return new_state, arena_metrics(new_state["lam_s"], x_K, x_s_row, keep_c) | fm


def _round_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches, return_trace):
    """GPDMM round over the flat arena: the tail is 3 fused kernels + the
    single client-mean all-reduce instead of ~6 per-leaf pytree passes.

    The stacked hot state (lam_s, x_c, u_hat) is arena-RESIDENT: it enters
    and leaves the round as ``(m, width)`` buffers (donated in place by the
    launchers), so the only per-round layout work is packing the
    server-sized x_s row -- 1/m of the state."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam = state["lam_s"]
    x_c = state["x_c"]
    m = lam.shape[0]
    if use_cohort(cfg, m) and not return_trace:
        # trace consumers need the full-population x_K/x_ref stacking, so
        # traced rounds stay on the masked path
        return _round_arena_cohort(cfg, state, grad_fn, batch, per_step_batches)
    x_s_row = spec.pack(state["x_s"])

    snapshot = None
    if cfg.variance_reduction == "svrg":
        snapshot = jnp.broadcast_to(x_s_row[None], x_c.shape)
    x_K, x_sum = inner_steps_arena(
        spec, grad_fn, x_c, x_s_row, lam, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches, vr_snapshot=snapshot,
        average=cfg.use_avg or return_trace,
    )
    x_ref, scale = uplink_ref(cfg, spec, grad_fn, per_step_batches, x_K, x_sum)

    # fused tail pass 1: the uplink (and lam_is only when a trace wants it --
    # 3 reads + 1 write on the training path, +1 write with the trace)
    with jax.named_scope("round.uplink"):
        lam_is, uplink = ops.round_tail(x_ref, lam, x_s_row, rho,
                                        with_lam_is=return_trace, scale=scale)
    new_state, x_s_new, lam_s_new, mask, fm = arena_tail(cfg, spec, state, uplink, m)

    # silent clients did not really run their inner steps: keep their carry
    x_c_new = x_K if mask is None else jnp.where(mask[:, None], x_K, x_c)
    new_state |= {
        "x_s": spec.unpack(x_s_new),  # server-sized; clients stay packed
        "lam_s": lam_s_new,
        "x_c": x_c_new,
        "round": state["round"] + 1,
    }
    metrics = arena_metrics(lam_s_new, x_K, x_s_row, mask) | fm
    if return_trace:
        s_bar = avg_scale(spec, grad_fn, K, per_step=per_step_batches,
                          svrg=snapshot is not None)
        x_bar = x_sum if s_bar is None else x_sum * s_bar
        metrics["trace"] = {
            "x_ref": spec.unpack_stacked(x_bar if cfg.use_avg else x_K),
            "x_bar": spec.unpack_stacked(x_bar),
            "lam_is": spec.unpack_stacked(lam_is),
            "x_K": spec.unpack_stacked(x_K),
        }
    return new_state, metrics


def _round(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False, return_trace=False):
    if use_arena(cfg, state["x_s"]):
        return _round_arena(cfg, state, grad_fn, batch, per_step_batches, return_trace)
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    x_s, lam_s, x_c = state["x_s"], state["lam_s"], state["x_c"]
    m = jax.tree.leaves(lam_s)[0].shape[0]
    x_s_b = T.tree_broadcast(x_s, m)

    x_K, x_bar = inner_steps(
        grad_fn, x_c, x_s_b, lam_s, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches,
        vr_snapshot=x_s_b if cfg.variance_reduction == "svrg" else None,
    )
    x_ref = x_bar if cfg.use_avg else x_K

    lam_is = T.tmap(lambda s, xr, l: rho * (s - xr) - l, x_s_b, x_ref, lam_s)
    uplink = T.tmap(lambda xr, l: xr - l / rho, x_ref, lam_is)
    new_state = {}
    if cfg.uplink_bits is not None:  # beyond-paper: EF21 delta-quantised uplink
        uplink = T.tree_quantize_delta(uplink, state["u_hat"], cfg.uplink_bits)
    # the robustness layer is layout-independent: the same inject ->
    # participation -> screen -> combined-select pipeline as arena_tail
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
        # silent clients transmit nothing; the server keeps its cached view
        uplink = T.tree_select(mask, uplink, state["u_hat"])
    if "u_hat" in state:
        new_state["u_hat"] = uplink  # the server's per-client view
    x_s_new = T.tree_client_mean(uplink)  # <- the round's single all-reduce
    x_s_new_b = T.tree_broadcast(x_s_new, m)
    # lam_{s|i}^{r+1} = rho (x_ref - x_s) - lam_{i|s} == rho (u_i - x_s):
    # reconstructed from the TRANSMITTED uplink, so the quantised variant
    # stays faithful to what a real server would see (it cannot separate
    # x_ref from lam_{i|s} inside u_i).
    lam_s_new = T.tmap(lambda u, s: rho * (u - s), uplink, x_s_new_b)

    # silent clients did not really run their inner steps: keep their carry
    x_c_new = x_K if mask is None else T.tree_select(mask, x_K, x_c)
    new_state |= {"x_s": x_s_new, "lam_s": lam_s_new, "x_c": x_c_new, "round": state["round"] + 1}
    metrics = {
        # KKT invariant (25): sum_i lam_{s|i} == 0 identically
        "lam_sum_norm": T.tree_norm(T.tree_client_sum(lam_s_new)),
        # silent clients keep their carry, so drift averages the ACTIVE set
        "client_drift": T.masked_client_mean(
            T.tree_client_sqnorms(T.tree_sub(x_K, x_s_b)), mask),
        "used_arena": jnp.zeros((), jnp.float32),
    }
    if fplan is not None or keep is not None:
        tx = faults.combine_mask(pmask, fplan, None)
        if faults.async_on(cfg):
            tx = staleness.fresh_mask(tx, fplan)
        metrics |= faults.fault_metrics(fplan, tx, keep) | sm
    if return_trace:  # quantities the convergence-theory checks need
        metrics["trace"] = {"x_ref": x_ref, "x_bar": x_bar, "lam_is": lam_is, "x_K": x_K}
    return new_state, metrics


def make(cfg: FederatedConfig) -> FedOpt:
    def init(params, m):
        if use_arena(cfg, params):
            # arena-resident client state: one (m, width) buffer per stacked
            # tensor, donated in place round over round; x_s stays a pytree
            # (the public server-params contract)
            spec = arena.ArenaSpec.from_tree(params)
            row = spec.pack(params)
            st = {
                "x_s": params,
                "lam_s": arena.zeros(spec, m),
                "x_c": jnp.broadcast_to(row[None], (m, spec.width)),
                "round": jnp.zeros((), jnp.int32),
            }
            if (cfg.uplink_bits is not None or cfg.participation < 1.0
                    or faults.needs_cache(cfg)):
                st["u_hat"] = jnp.broadcast_to(row[None], (m, spec.width))
            if faults.async_on(cfg):
                st |= staleness.init_arena(spec, m)
            return st
        st = {
            "x_s": params,
            "lam_s": T.tree_zeros_like(T.tree_broadcast(params, m)),
            "x_c": T.tree_broadcast(params, m),  # x_i^{0,K} = x_s^1 (Alg. 1)
            "round": jnp.zeros((), jnp.int32),
        }
        if (cfg.uplink_bits is not None or cfg.participation < 1.0
                or faults.needs_cache(cfg)):
            # server's running view of each client's uplink (EF21 integrator /
            # async-PDMM cache / fault-silence fallback); init == round-0
            # uplink x_c - 0/rho.  A fresh broadcast, NOT an alias of x_c:
            # donated round states must not contain the same buffer twice.
            st["u_hat"] = T.tree_broadcast(params, m)
        if faults.async_on(cfg):
            st |= staleness.init_tree(params, m)
        return st

    return FedOpt(
        name="gpdmm",
        init=init,
        round=partial(_round, cfg),
        server_params=lambda s: s["x_s"],
    )
