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

The bodies here serve the whole family: with ``accel`` bound they run
AGPDMM (Alg. 2, ``core.agpdmm``), which starts every round at the server
row x_s^r and uplinks from x_i^{r,K}, so no x_c is stored, gathered,
scattered or staged.  Each layout has one body: the stacked pytree
(``_family_round``), the masked arena (``_round_arena``), the sampled
cohort (``_round_arena_cohort``) and the host popstore
(``popstore_body``).  What of the uplink reaches the server -- EF21, faults,
participation, the screen, stale rows -- is decided in one place,
``core.uplink.admit``; the bodies keep the family's own server step, the
mean and the dual refresh (``server_tail``).

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
from repro.core.uplink import admit, participation_key
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


def uplink_ref(avg: bool, cfg: FederatedConfig, spec, grad_fn, per_step, x_K,
               x_sum):
    """``(x_ref, scale)`` for ``ops.round_tail``: the average of the inner
    iterates (eq. 23) as ``inner_steps_arena``'s sum and its factor, or x_K
    (eq. 24: ``use_avg=False``, and AGPDMM) as it is."""
    if not avg:
        return x_K, None
    return x_sum, avg_scale(spec, grad_fn, cfg.inner_steps, per_step=per_step,
                            svrg=cfg.variance_reduction == "svrg")


def server_tail(rho, uplink):
    """The family's server step over the admitted ``(m, width)`` uplink
    buffer: the round's single client-mean all-reduce and the fused dual
    refresh.  Returns ``(x_s_new_row, lam_s_new)``."""
    with jax.named_scope("round.server_mean"):
        x_s_new = jnp.mean(uplink, axis=0)  # <- the round's single all-reduce
    # fused tail pass 2: lam' = rho (u - x_s'), server row broadcast in-kernel
    with jax.named_scope("round.dual_refresh"):
        lam_s_new = ops.dual_from_uplink(uplink, x_s_new, rho)
    return x_s_new, lam_s_new


def arena_metrics(adm, x_K, x_s_row, lam_s_new=None):
    """Round metrics straight off the arena buffers, with the admission's
    counters.  ``lam_sum_norm``, GPDMM's KKT invariant, where the round has
    its dual (padding columns are identically zero, so no masking is needed
    there).  ``client_drift`` averages over the ADMITTED rows only
    (``adm.mask``, or every row of ``x_K`` when None): silent clients' x_K
    is computed-then-discarded (the carry is kept), so averaging it in
    would report movement that never entered the state.  ``used_arena``
    records the (static) layout decision so benches can see which path a
    round actually ran."""
    f32 = jnp.float32
    with jax.named_scope("round.metrics"):
        out = {} if lam_s_new is None else {
            "lam_sum_norm": jnp.linalg.norm(jnp.sum(lam_s_new.astype(f32), axis=0))}
        out |= {
            "client_drift": T.masked_client_mean(
                jnp.sum(jnp.square((x_K - x_s_row[None]).astype(f32)), axis=1),
                adm.mask),
            "used_arena": jnp.ones((), f32),
        }
    return out | adm.metrics


def _kept(mask, x_K, x0):
    """The new carry rows: silent and demoted clients did not really run
    their inner steps, so they keep their round-start row."""
    return x_K if mask is None else jnp.where(mask[:, None], x_K, x0)


def _cohort_uplink(cfg: FederatedConfig, spec, grad_fn, per_step, accel,
                   x_s_row, lam_c, x0_c, batch_c, idx):
    """The cohort's inner loop and uplink, shared by the device cohort round
    and the popstore body: ``(x_K, uplink)`` over the cohort's rows, tiled
    by ``cohort_tile`` when set.  GPDMM starts from the cohort's carry rows
    ``x0_c``; AGPDMM (``accel``) from the server row."""
    rho = resolved_rho(cfg)
    avg = cfg.use_avg and not accel
    eta_v = _eta_val(cfg.eta)
    per_client = np.ndim(eta_v) > 0

    def inner(rows, b):
        lam_t = rows[0]
        # per-client eta rides the rows tuple so the cohort tiler slices it
        # alongside the state rows (a closure capture would stay
        # cohort-sized inside a tile-sized call)
        eta_t = rows[1] if per_client else eta_v
        x0 = jnp.broadcast_to(x_s_row[None], lam_t.shape) if accel else rows[-1]
        snap = (jnp.broadcast_to(x_s_row[None], x0.shape)
                if cfg.variance_reduction == "svrg" else None)
        return inner_steps_arena(
            spec, grad_fn, x0, x_s_row, lam_t, b, K=cfg.inner_steps, eta=eta_t,
            rho=rho, per_step=per_step, vr_snapshot=snap, average=avg,
        )

    rows = ((lam_c,) + ((jnp.asarray(eta_v)[idx],) if per_client else ())
            + (() if accel else (x0_c,)))
    x_K, x_sum = run_cohort_inner(cfg, inner, rows, batch_c, per_step=per_step)
    x_ref, scale = uplink_ref(avg, cfg, spec, grad_fn, per_step, x_K, x_sum)
    with jax.named_scope("round.uplink"):
        _, uplink = ops.round_tail(x_ref, lam_c, x_s_row, rho,
                                   with_lam_is=False, scale=scale)
    return x_K, uplink


def popstore_body(cfg: FederatedConfig, spec, m: int, grad_fn, per_step,
                  accel=False):
    """Device half of a host-popstore GPDMM round (see ``core.popstore``);
    ``accel`` makes it AGPDMM's.

    The returned ``body(server, staged, idx, round_idx, batch)`` touches
    ONLY O(cohort) device memory: ``staged`` carries the sampled rows of the
    host store (``u_hat`` -- the server's cached uplink view -- and, for
    GPDMM, ``x_c``, the primal carry), and the dual rows are reconstructed
    LAZILY via the round invariant lam_{s|i} = rho (u_hat_i - x_s)
    (``ops.dual_from_uplink`` on the staged rows -- elementwise, so
    bit-identical to gathering rows of the dense refresh the arena path
    materialises).  No O(m) tail runs here: the host driver
    (``core.popstore.Runner``) scatters the returned rows into the store and
    maintains the server mean incrementally.  Returns ``(rows_out,
    server_rows, metrics)`` where ``rows_out`` (``u_hat``, and ``x_c`` for
    GPDMM) scatters back into the host store."""
    rho = resolved_rho(cfg)

    def body(server, staged, idx, round_idx, batch):
        x_s_row = spec.pack(server["x_s"])
        u_hat_c = staged["u_hat"]
        x0_c = None if accel else staged["x_c"]
        lam_c = ops.dual_from_uplink(u_hat_c, x_s_row, rho)  # lazy dual
        x_K, uplink = _cohort_uplink(
            cfg, spec, grad_fn, per_step, accel, x_s_row, lam_c, x0_c,
            cohort_batch(batch, idx, m, per_step), idx)
        adm = admit(cfg, uplink, arena=spec, round_idx=round_idx, m=m,
                    ref=x_s_row, fallback=u_hat_c, idx=idx)
        rows_out = {"u_hat": adm.admitted}
        if not accel:
            rows_out["x_c"] = _kept(adm.mask, x_K, x0_c)
        return rows_out, {}, arena_metrics(adm, x_K, x_s_row)

    return body


def _round_arena_cohort(cfg: FederatedConfig, state, grad_fn, batch,
                        per_step_batches, accel):
    """GPDMM round over the SAMPLED COHORT (ISSUE 5): gather the round's
    active rows out of the population arena, run the fused inner loop +
    round tail on the ``(m_active, width)`` cohort buffer (tiled via
    ``cohort_tile`` when set), scatter the updated rows back.  Compute and
    gradient-batch traffic scale with the cohort, not the population; the
    O(m) work that remains is inherent to the algorithm (every client's
    lam_{s|i} moves with the new x_s, and the server mean reads every cached
    u_hat row).  AGPDMM (``accel``) gathers and scatters no carry rows.

    Row-for-row identical to the masked path: the cohort rows see the same
    per-row kernels, and the server mean is taken over the SCATTERED
    population buffer -- the same mean-of-selected-rows the masked path
    computes, realising (sum_active uplink + sum_silent u_hat) / m without a
    reordered reduction (tests/test_cohort.py pins this per round)."""
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam, u_hat = state["lam_s"], state["u_hat"]  # participation < 1 caches
    m = lam.shape[0]
    x_s_row = spec.pack(state["x_s"])
    idx, _mask = T.cohort_indices(
        participation_key(cfg, state["round"]), m, cfg.participation
    )
    lam_c = ops.row_gather(lam, idx)
    x0_c = None if accel else ops.row_gather(state["x_c"], idx)
    x_K, uplink = _cohort_uplink(
        cfg, spec, grad_fn, per_step_batches, accel, x_s_row, lam_c, x0_c,
        cohort_batch(batch, idx, m, per_step_batches), idx)
    adm = admit(cfg, uplink, arena=spec, round_idx=state["round"], m=m,
                ref=x_s_row, fallback=ops.row_gather(u_hat, idx), idx=idx)
    u_hat_new = ops.row_scatter(u_hat, idx, adm.admitted)
    # ONE mean over the scattered buffer, so it matches the masked path
    x_s_new, lam_s_new = server_tail(resolved_rho(cfg), u_hat_new)
    new_state = {
        "u_hat": u_hat_new,
        "x_s": spec.unpack(x_s_new),
        "lam_s": lam_s_new,
        "round": state["round"] + 1,
    }
    if not accel:
        # demoted cohort rows are silent, full stop: the carry keeps its
        # round-start row exactly as a never-sampled client's does
        new_state["x_c"] = ops.row_scatter(state["x_c"], idx,
                                           _kept(adm.mask, x_K, x0_c))
    return new_state, arena_metrics(adm, x_K, x_s_row, lam_s_new)


def _round_arena(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches,
                 return_trace, accel):
    """GPDMM round over the flat arena: the tail is 3 fused kernels + the
    single client-mean all-reduce instead of ~6 per-leaf pytree passes.

    The stacked hot state (lam_s, x_c, u_hat) is arena-RESIDENT: it enters
    and leaves the round as ``(m, width)`` buffers (donated in place by the
    launchers), so the only per-round layout work is packing the
    server-sized x_s row -- 1/m of the state.  AGPDMM (``accel``) starts
    every row at the server row and keeps no x_c."""
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    spec = arena.ArenaSpec.from_tree(state["x_s"])
    lam = state["lam_s"]
    m = lam.shape[0]
    if use_cohort(cfg, m) and not return_trace:
        # trace consumers need the full-population x_K/x_ref stacking, so
        # traced rounds stay on the masked path
        return _round_arena_cohort(cfg, state, grad_fn, batch, per_step_batches,
                                   accel)
    x_s_row = spec.pack(state["x_s"])
    avg = cfg.use_avg and not accel
    x0 = jnp.broadcast_to(x_s_row[None], (m, spec.width)) if accel else state["x_c"]

    snapshot = None
    if cfg.variance_reduction == "svrg":
        snapshot = jnp.broadcast_to(x_s_row[None], x0.shape)
    x_K, x_sum = inner_steps_arena(
        spec, grad_fn, x0, x_s_row, lam, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches, vr_snapshot=snapshot,
        average=avg or return_trace,
    )
    x_ref, scale = uplink_ref(avg, cfg, spec, grad_fn, per_step_batches, x_K,
                              x_sum)

    # fused tail pass 1: the uplink (and lam_is only when a trace wants it --
    # 3 reads + 1 write on the training path, +1 write with the trace)
    with jax.named_scope("round.uplink"):
        lam_is, uplink = ops.round_tail(x_ref, lam, x_s_row, rho,
                                        with_lam_is=return_trace, scale=scale)
    adm = admit(cfg, uplink, arena=spec, round_idx=state["round"], m=m,
                ref=x_s_row, fallback=state.get("u_hat"), state=state)
    x_s_new, lam_s_new = server_tail(rho, adm.admitted)
    new_state = adm.state | {
        "x_s": spec.unpack(x_s_new),  # server-sized; clients stay packed
        "lam_s": lam_s_new,
        "round": state["round"] + 1,
    }
    if "u_hat" in state:
        new_state["u_hat"] = adm.admitted
    if not accel:
        new_state["x_c"] = _kept(adm.mask, x_K, x0)
    metrics = arena_metrics(adm, x_K, x_s_row, lam_s_new)
    if return_trace:
        s_bar = avg_scale(spec, grad_fn, K, per_step=per_step_batches,
                          svrg=snapshot is not None)
        x_bar = x_sum if s_bar is None else x_sum * s_bar
        metrics["trace"] = {
            "x_ref": spec.unpack_stacked(x_bar if avg else x_K),
            "x_bar": spec.unpack_stacked(x_bar),
            "lam_is": spec.unpack_stacked(lam_is),
            "x_K": spec.unpack_stacked(x_K),
        }
    return new_state, metrics


def _family_round(accel, cfg: FederatedConfig, state, grad_fn, batch,
                  per_step_batches=False, return_trace=False):
    """One round of the GPDMM family, on the layout ``use_arena`` picks.
    ``accel`` selects AGPDMM (Alg. 2): each round starts at the server row
    and uplinks from x_K (eq. 24), so no x_c is kept; otherwise GPDMM
    (Alg. 1) starts from the carried x_c."""
    if use_arena(cfg, state["x_s"]):
        return _round_arena(cfg, state, grad_fn, batch, per_step_batches,
                            return_trace, accel)
    rho = resolved_rho(cfg)
    K = cfg.inner_steps
    x_s, lam_s = state["x_s"], state["lam_s"]
    m = jax.tree.leaves(lam_s)[0].shape[0]
    x_s_b = T.tree_broadcast(x_s, m)
    x0 = x_s_b if accel else state["x_c"]

    x_K, x_bar = inner_steps(
        grad_fn, x0, x_s_b, lam_s, batch, K=K, eta=cfg.eta, rho=rho,
        per_step=per_step_batches,
        vr_snapshot=x_s_b if cfg.variance_reduction == "svrg" else None,
    )
    x_ref = x_bar if cfg.use_avg and not accel else x_K

    lam_is = T.tmap(lambda s, xr, l: rho * (s - xr) - l, x_s_b, x_ref, lam_s)
    uplink = T.tmap(lambda xr, l: xr - l / rho, x_ref, lam_is)
    adm = admit(cfg, uplink, arena=None, round_idx=state["round"], m=m,
                ref=x_s, fallback=state.get("u_hat"), state=state)
    x_s_new = T.tree_client_mean(adm.admitted)  # <- the round's single all-reduce
    x_s_new_b = T.tree_broadcast(x_s_new, m)
    # lam_{s|i}^{r+1} = rho (x_ref - x_s) - lam_{i|s} == rho (u_i - x_s):
    # reconstructed from the TRANSMITTED uplink, so the quantised variant
    # stays faithful to what a real server would see (it cannot separate
    # x_ref from lam_{i|s} inside u_i).
    lam_s_new = T.tmap(lambda u, s: rho * (u - s), adm.admitted, x_s_new_b)

    new_state = adm.state | {"x_s": x_s_new, "lam_s": lam_s_new,
                             "round": state["round"] + 1}
    if "u_hat" in state:
        new_state["u_hat"] = adm.admitted  # the server's per-client view
    if not accel:
        # silent clients did not really run their inner steps: keep their carry
        new_state["x_c"] = (x_K if adm.mask is None
                            else T.tree_select(adm.mask, x_K, state["x_c"]))
    metrics = {
        # KKT invariant (25): sum_i lam_{s|i} == 0 identically
        "lam_sum_norm": T.tree_norm(T.tree_client_sum(lam_s_new)),
        # silent clients keep their carry, so drift averages the ACTIVE set
        "client_drift": T.masked_client_mean(
            T.tree_client_sqnorms(T.tree_sub(x_K, x_s_b)), adm.mask),
        "used_arena": jnp.zeros((), jnp.float32),
    } | adm.metrics
    if return_trace:  # quantities the convergence-theory checks need
        metrics["trace"] = {"x_ref": x_ref, "x_bar": x_bar, "lam_is": lam_is, "x_K": x_K}
    return new_state, metrics


def _round(cfg: FederatedConfig, state, grad_fn, batch, per_step_batches=False, return_trace=False):
    """GPDMM's round (Alg. 1): the family round from the carried x_c."""
    return _family_round(False, cfg, state, grad_fn, batch, per_step_batches,
                         return_trace)


def make(cfg: FederatedConfig, *, accel=False) -> FedOpt:
    """GPDMM's optimiser; ``accel=True`` makes AGPDMM's (``core.agpdmm``),
    whose state has no x_c."""
    def init(params, m):
        cache = (cfg.uplink_bits is not None or cfg.participation < 1.0
                 or faults.needs_cache(cfg))
        if use_arena(cfg, params):
            # arena-resident client state: one (m, width) buffer per stacked
            # tensor, donated in place round over round; x_s stays a pytree
            # (the public server-params contract)
            spec = arena.ArenaSpec.from_tree(params)
            row = spec.pack(params)
            st = {
                "x_s": params,
                "lam_s": arena.zeros(spec, m),
                "round": jnp.zeros((), jnp.int32),
            }
            if not accel:
                st["x_c"] = jnp.broadcast_to(row[None], (m, spec.width))
            if cache:
                st["u_hat"] = jnp.broadcast_to(row[None], (m, spec.width))
            if faults.async_on(cfg):
                st |= staleness.init_arena(spec, m)
            return st
        st = {
            "x_s": params,
            "lam_s": T.tree_zeros_like(T.tree_broadcast(params, m)),
            "round": jnp.zeros((), jnp.int32),
        }
        if not accel:
            st["x_c"] = T.tree_broadcast(params, m)  # x_i^{0,K} = x_s^1 (Alg. 1)
        if cache:
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
        round=partial(_family_round, True, cfg) if accel else partial(_round, cfg),
        server_params=lambda s: s["x_s"],
    )
