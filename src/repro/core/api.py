"""Unified federated-optimiser interface (the paper's technique as a
first-class, model-agnostic JAX module).

Every algorithm is a pair of pure functions:

    init(params, m)                  -> state          (pytree)
    round(state, grad_fn, batch)     -> (state, metrics)

with the conventions:
  * ``params`` is any pytree (a scalar vector for the paper's experiments or a
    full transformer parameter tree);
  * per-client entries in ``state`` are stacked with a leading client dim m;
  * ``grad_fn(params_i, batch_i) -> grad`` is the per-client gradient oracle;
    ``round`` maps it over the client dim, so the same code runs the paper's
    least-squares problems and sharded LM training;
  * ``batch`` leaves have leading dim m, or (K, m, ...) when
    ``per_step_batches=True`` (one minibatch per inner gradient step, the
    paper's softmax-regression setup).

The exact (prox-based) PDMM / FedSplit variants instead take a
``prox_fn(v, rho) -> argmin_x f_i(x) + rho/2 ||x - v||^2`` oracle (vmapped the
same way); they live in ``core.pdmm`` / ``core.fedsplit``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FederatedConfig
from repro.sharding.constraints import per_client


class FedOpt(NamedTuple):
    name: str
    init: Callable  # (params, m) -> state
    round: Callable  # (state, grad_fn, batch, per_step_batches=False) -> (state, metrics)
    server_params: Callable  # (state) -> params  (current global estimate)


# ---------------------------------------------------------------------------
# gradient-oracle protocol (arena-native fast paths)
# ---------------------------------------------------------------------------
#
# A plain ``grad_fn(params_i, batch_i) -> grad`` works everywhere; the arena
# hot path additionally recognises two OPTIONAL attributes on the callable:
#
#   grad_fn.grad_arena(spec)          -> ga(x_arena, batch) -> g_arena
#       Stacked gradient evaluated DIRECTLY on the packed ``(m, width)``
#       buffer via the spec's slice table.  Padding columns must map to 0.
#       Removes the per-inner-step unpack -> vgrad -> pack boundary round
#       trip (+4 full-state HBM passes/step for multi-leaf trees).
#
#   grad_fn.affine_arena(spec, batch) -> (H, c)   with H (m, W, W), c (m, W)
#       Declares the gradient affine: grad_i(x) = H_i x - c_i in arena
#       coordinates (rows/cols beyond each leaf's size must be zero so the
#       padding invariant survives).  Lets the round run the WHOLE K-step
#       inner loop as one fused kernel (``kernels/inner_loop.py``) that
#       keeps the client row in VMEM across all K steps.  Any AFFINE-OFFSET
#       client correction (SCAFFOLD's ``grad f_i(x) - c_i + c``) stays on
#       this path: the offset folds into the affine constant, so the
#       consumer passes the arena-resident correction buffer straight to the
#       kernel's per-client offset row (``inner_loop_affine(..., off=...)``)
#       -- no extra (m, width) materialisation, no per-step re-read.
#
#   grad_fn.curvature_arena(spec)     -> curv(x_arena, batch) -> L (m,)
#       Per-client smoothness estimates in arena coordinates (the auto-eta
#       stepsize derivation, ``core.autotune``).  ``x_arena`` is the packed
#       (m, width) point the curvature is probed at (affine oracles ignore
#       it).  When absent, ``autotune.estimate_L`` falls back to a power
#       iteration on ``affine_arena``'s H blocks, then to a Hessian-vector
#       power iteration through ``jax.jvp`` of the arena (or plain) grad.
#
# ``make_oracle`` assembles such an annotated callable; ``arena_grad``
# resolves the best available stacked arena gradient for any grad_fn, and
# ``affine_case`` gates the fused K-step kernel (shared by GPDMM/AGPDMM and
# the SCAFFOLD/FedAvg offset variant).


def make_oracle(grad_fn, *, grad_arena=None, affine_arena=None,
                curvature_arena=None):
    """Annotate a per-client ``grad_fn`` with arena-native fast paths."""

    def oracle(x, batch):
        return grad_fn(x, batch)

    if grad_arena is not None:
        oracle.grad_arena = grad_arena
    if affine_arena is not None:
        oracle.affine_arena = affine_arena
    if curvature_arena is not None:
        oracle.curvature_arena = curvature_arena
    return oracle


def arena_grad(grad_fn, spec):
    """Resolve the stacked arena-space gradient for ``grad_fn``.

    Returns ``(ga, native)`` where ``ga((m, width), batch) -> (m, width)``.
    Oracles advertising ``grad_arena`` run entirely in arena space (0 extra
    full-state passes); plain grads are mapped over the client rows through
    the pytree boundary (unpack x + pack g: +4 passes per step for
    multi-leaf trees).

    The plain path is a ``lax.map`` over rows, not a ``vmap`` over the
    stacked tree: one client's activations are live at a time, and the TPU
    compile of a model's gradient stays the single-client one (a vmapped
    full-width LM gradient took minutes to compile for a v5e).  Under a
    client-sharded mesh each device maps over its own rows (``per_client``).
    """
    factory = getattr(grad_fn, "grad_arena", None)
    if factory is not None:
        return factory(spec), True

    def one(row, bi):
        with jax.named_scope("arena_pack"):
            params = spec.unpack(row)
        g = grad_fn(params, bi)
        with jax.named_scope("arena_pack"):
            return spec.pack(g)

    def ga(xa, b):
        return map_clients(one, xa, b)

    return ga, False


def step_by_client(spec, grad_fn, update, x, batch, rows, shared, vr=None):
    """One inner step of every client, a client at a time: client i's
    gradient is taken at its row of the ``(m, width)`` arena ``x``, packed,
    and applied at once by ``update(x, g_i, i, *rows, *shared)``, which
    returns ``x`` with row i stepped.  One client's gradient row is live at
    a time.  ``rows`` lead with the client dim (the duals, per-client
    stepsizes), ``shared`` are replicated (the server row); under a
    client-sharded mesh each device steps its own clients (``per_client``).
    ``vr = (snapshot, gbar)``, both ``(m, width)``: SVRG, client i's
    gradient corrected by ``gbar_i - grad f_i(snapshot_i)`` on the same
    batch."""
    b_leaves, b_def = jax.tree.flatten(batch)
    nb = len(b_leaves)
    snap, gbar = vr if vr is not None else (None, None)
    row = lambda a, i: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)  # noqa: E731

    def local(x, snap, gbar, *args):
        b = jax.tree.unflatten(b_def, args[:nb])

        def body(i, x):
            bi = jax.tree.map(lambda t: row(t, i), b)
            with jax.named_scope("round.client_grad"):
                with jax.named_scope("arena_pack"):
                    params = spec.unpack(row(x, i))
                g = grad_fn(params, bi)
                if snap is not None:
                    with jax.named_scope("arena_pack"):
                        g_snap = grad_fn(spec.unpack(row(snap, i)), bi)
                    g = jax.tree.map(jnp.subtract, g, g_snap)
                with jax.named_scope("arena_pack"):
                    g = spec.pack(g)
                if gbar is not None:
                    g = g + row(gbar, i)
            with jax.named_scope("round.client_update"):
                return update(x, g, i, *args[nb:])

        return jax.lax.fori_loop(0, x.shape[0], body, x)

    return per_client(local, (x, snap, gbar, *b_leaves, *rows), shared)


def map_clients(fn, x, batch):
    """``fn(x_i, batch_i)`` stacked over the leading client dim of ``x`` and
    ``batch`` (pytrees), one client at a time: a ``lax.map``, run by each
    device over its own clients under a client-sharded mesh
    (``per_client``).  Every layout computes client gradients this way, so
    the arena and pytree rounds see the same per-client arithmetic."""
    x_leaves, x_def = jax.tree.flatten(x)
    b_leaves, b_def = jax.tree.flatten(batch)
    n = len(x_leaves)

    def local(*leaves):
        return jax.lax.map(
            lambda a: fn(*a), (jax.tree.unflatten(x_def, leaves[:n]),
                               jax.tree.unflatten(b_def, leaves[n:])))

    return per_client(local, (*x_leaves, *b_leaves))


def use_arena(cfg: FederatedConfig, params=None) -> bool:
    """The shared layout-dispatch policy: does this (config, parameter tree)
    run the round on the flat client-state arena?  Every algorithm consults
    THIS function (it is cross-algorithm config/arena policy, not any one
    optimiser's logic).

    fsdp shards parameters per-leaf; packing would force a re-gather, so
    that layout keeps the per-leaf pytree path.  Mixed-dtype trees (bf16
    weights + f32 norms) also fall back: the single arena buffer would
    promote everything to the widest dtype -- 2x the client-state HBM and a
    numerical divergence from the per-leaf path.  ``use_arena="auto"``
    additionally keeps packed widths below ``arena_min_width`` on the pytree
    path: below the threshold the per-round pack/dispatch overhead outweighs
    the fused kernels (measured in BENCH_round.json).  The decision is
    static (spec = shapes only) and recorded in round metrics as
    ``used_arena``.
    """
    if cfg.use_arena is False or cfg.layout == "fsdp":
        return False
    if params is not None:
        if len({leaf.dtype for leaf in jax.tree.leaves(params)}) > 1:
            return False
    if cfg.use_arena == "auto" and params is not None:
        from repro.core import arena

        return arena.ArenaSpec.from_tree(params).width >= cfg.arena_min_width
    return True


def affine_case(grad_fn, spec, *, per_step=False, svrg=False):
    """Gate the fused K-step affine kernel for ``grad_fn`` on ``spec``.

    Returns the oracle's ``affine_arena`` factory when the whole inner loop
    can run as ONE kernel -- the oracle declares the affine structure, the
    batch is shared across steps (no per-step minibatches, no SVRG
    correction), and one client's (W, W) H block fits the VMEM budget --
    else None (callers fall back to the step-at-a-time scan).  Static:
    decidable from shapes alone, so it costs nothing inside jit.
    """
    affine = getattr(grad_fn, "affine_arena", None)
    if affine is None or per_step or svrg:
        return None
    from repro.kernels import ops

    return affine if ops.affine_inner_fits(spec.width) else None


# ---------------------------------------------------------------------------
# cohort-sampled round engine (shared gather/scatter + mask plumbing)
# ---------------------------------------------------------------------------
#
# With ``participation < 1`` the masked round still pays O(m_total): every
# client row runs the fused K-step inner loop and the silent results are
# discarded at the tail.  The cohort engine (ISSUE 5) gathers the round's
# active rows out of the population arena, runs the SAME fused kernels on the
# (m_active, width) cohort buffer, and scatters the updated rows back; the
# server mean is taken over the scattered population buffer, which makes it
# the documented (sum_active uplink + sum_silent u_hat) / m identity and
# keeps it bit-identical to the masked path's mean-of-selected-rows.  The
# helpers below are the cross-algorithm plumbing; the per-algorithm cohort
# rounds live next to their masked siblings in gpdmm (which also serves
# agpdmm), scaffold and fedavg, and all of them admit their uplink through
# core.uplink.admit.


# algorithms with a cohort round implementation (the four arena rounds);
# fedsplit and the graph subsystem keep their previous participation
# semantics, so the launchers must never shrink their batches
COHORT_ALGOS = ("gpdmm", "agpdmm", "scaffold", "fedavg")


def use_cohort(cfg: FederatedConfig, m: int) -> bool:
    """Static policy: does this round run the cohort-sampled engine?

    Callers are the ARENA rounds of the four ``COHORT_ALGOS`` (the pytree
    path always masks -- a per-leaf gather/scatter would re-materialise the
    tree per round), plus the launchers deciding batch sizing -- hence the
    algorithm/topology guard lives HERE, not in the callers.  With
    ``cohort="auto"`` the engine engages whenever participation < 1 and the
    cohort is strictly smaller than the population (gathering all rows would
    add two copies for nothing); ``True`` forces it, ``False`` keeps the
    masked full-population path (the conformance oracle)."""
    # truthiness, not identity: validation admits cohort=0/1 (int spellings
    # of the bools, e.g. from a JSON config layer) and 0 must mean False
    if cfg.participation >= 1.0 or not cfg.cohort:
        return False
    if cfg.algorithm not in COHORT_ALGOS or cfg.topology != "star":
        return False
    # the bounded-staleness engine (core.staleness) needs the FULL population
    # each round -- a delayed client outside the cohort still has a slot to
    # age/arrive -- so async rounds pin the masked full-population path
    from repro.core import faults

    if faults.async_on(cfg):
        return False
    if cfg.cohort == "auto":
        from repro.core import tree_util as T

        return T.cohort_count(m, cfg.participation) < m
    return True


def use_popstore(cfg: FederatedConfig, m: int) -> bool:
    """Static policy: does this run keep the population's resident client
    state in the HOST store (``core.popstore``) instead of device arenas?

    The store rides the cohort engine (same participation draw, same
    gather/scatter row contract), so it engages only where ``use_cohort``
    does -- callers additionally gate on ``use_arena`` exactly as they do
    for the cohort engine itself.  ``popstore="auto"`` moves the state off
    device once the population reaches ``popstore_min_clients`` (below
    that the O(m) device buffers are cheap and per-round host<->device
    staging is pure overhead); ``True`` forces the store whenever the
    cohort engine runs, ``False`` never uses it.  The popstore round is a
    HOST-side driver (``popstore.Runner``) -- it cannot run inside an
    outer jit, which is why the launchers dispatch on this policy instead
    of ``FedOpt.round`` doing so internally."""
    if cfg.popstore is False or not use_cohort(cfg, m):
        return False
    if cfg.popstore == "auto":
        return m >= cfg.popstore_min_clients
    return True


def cohort_batch(batch, idx, m: int, per_step: bool):
    """Resolve the cohort's gradient batch.  Population-sized batch leaves
    (client dim == m) are row-gathered by ``idx``; leaves already sized to
    the cohort (a cohort-aware data stream, rows sorted by client id --
    ``tree_util.cohort_indices``'s order) pass through untouched, so at
    population scale no one has to materialise batches for silent clients.
    The client dim is axis 0, or axis 1 for per-step ``(K, m, ...)``
    batches.  Static decision (shapes only)."""
    axis = 1 if per_step else 0
    mc = idx.shape[0]

    def one(x):
        if x.shape[axis] == mc and mc != m:
            return x
        if x.shape[axis] != m:
            # a hard error, not an assert: under python -O an assert
            # vanishes and jnp.take's clamped gather would silently train
            # on duplicated rows
            raise ValueError(
                f"batch leaf client dim {x.shape[axis]} matches neither the "
                f"population ({m}) nor the cohort ({mc})")
        return jnp.take(x, idx, axis=axis)

    return jax.tree.map(one, batch)


def map_cohort_tiles(tile: int, fn, rows: tuple, batch, *, per_step: bool = False):
    """Run ``fn(rows_tile, batch_tile)`` over fixed-size tiles of the cohort
    via ``lax.map`` so peak live inner-loop state (the (tile, W, W) affine H
    blocks, per-step gradient temporaries) is O(tile), not O(m_active).

    ``rows``: tuple of ``(m_active, ...)`` arrays sliced along dim 0 (may be
    empty -- FedAvg carries no per-client rows; the tile count then comes
    from the batch).  ``batch`` leaves carry the client dim at axis 0 (or 1
    when ``per_step``).  ``fn`` returns any pytree of ``(tile, ...)`` arrays;
    outputs come back concatenated to ``(m_active, ...)``.  ``tile`` must
    divide the cohort size (checked; both are static)."""
    lead = [r.shape[0] for r in rows] or [
        jax.tree.leaves(batch)[0].shape[1 if per_step else 0]]
    mc = lead[0]
    if mc % tile:
        raise ValueError(f"cohort_tile={tile} must divide the cohort size {mc}")
    n = mc // tile
    rows_t = tuple(r.reshape((n, tile) + r.shape[1:]) for r in rows)

    def resh_batch(x):
        if per_step:  # (K, mc, ...) -> (n, K, tile, ...)
            k = x.shape[0]
            return jnp.moveaxis(x.reshape((k, n, tile) + x.shape[2:]), 1, 0)
        return x.reshape((n, tile) + x.shape[1:])

    batch_t = jax.tree.map(resh_batch, batch)
    out = jax.lax.map(lambda ab: fn(ab[0], ab[1]), (rows_t, batch_t))
    return jax.tree.map(lambda y: y.reshape((mc,) + y.shape[2:]), out)


def run_cohort_inner(cfg: FederatedConfig, fn, rows: tuple, batch, *,
                     per_step: bool = False):
    """Dispatch the cohort inner loop: tiled (``cfg.cohort_tile``) when the
    knob is set and smaller than the cohort, else one shot."""
    lead = [r.shape[0] for r in rows] or [
        jax.tree.leaves(batch)[0].shape[1 if per_step else 0]]
    tile = cfg.cohort_tile
    if tile is not None and tile < lead[0]:
        return map_cohort_tiles(tile, fn, rows, batch, per_step=per_step)
    return fn(rows, batch)


def resolved_rho(cfg: FederatedConfig) -> float:
    """The paper's default rho = 1/(K * eta) (matched to SCAFFOLD's scaling).

    rho is a SERVER-side quantity -- one penalty shared by the mean and the
    dual refresh -- so under per-client auto-eta (``eta`` resolved to a
    tuple by ``core.autotune``) the default derives from the MEAN of the
    per-client stepsizes.  Deriving it per client would hand every client
    its own penalty while the server still applies one rho in
    ``lam_s' = rho (u - x_s')``, silently desynchronising the dual refresh
    from the clients' inner steps -- pinned by ``tests/test_autotune.py``.
    Always a Python float (jit-static); raises on an unresolved "auto".
    """
    if cfg.rho is not None:
        return cfg.rho
    from repro.core import autotune

    rho = 1.0 / (cfg.inner_steps * autotune.mean_eta(cfg))
    assert rho > 0.0, rho
    return rho


def client_batches(batch, k: int, per_step: bool):
    """Yields the batch for inner step k (shared or per-step)."""
    if not per_step:
        return batch
    return jax.tree.map(lambda x: x[k], batch)


def make_scan_rounds(fed: FedOpt, grad_fn, per_step_batches: bool = False,
                     tol: float = 0.0):
    """Round-batched driver: returns ``run(state, batches) -> (state, metrics)``
    executing R full rounds inside ONE ``lax.scan`` (batch leaves carry a
    leading R dim; metrics come back stacked ``(R, ...)``).

    One jitted dispatch amortises the per-round launch overhead that
    dominates at small state sizes; with the state donated, XLA keeps the
    arena buffers in place across all R rounds.  State-identical to R
    separate ``fed.round`` calls (``tests/test_inner_loop.py``) -- the
    participation RNG is folded from the carried round counter, so masks
    match the loop-of-rounds schedule exactly.

    ``tol > 0`` (residual-based early termination, ``core.autotune``) adds
    the fused fixed-point residual of every round to the metrics
    (``res_dx2``/``res_x2``); the HOST loop between chunk dispatches applies
    the stopping rule -- the scan itself always runs its full R rounds.
    The gate is a static Python decision: ``tol=0`` compiles the identical
    fixed-budget graph, with no snapshot of the pre-round state alive.
    """

    def run(state, batches):
        def body(s, b):
            if tol > 0.0:
                from repro.core import autotune

                s2, metrics = fed.round(s, grad_fn, b, per_step_batches)
                return s2, {**metrics, **autotune.state_residual(s, s2)}
            return fed.round(s, grad_fn, b, per_step_batches)

        return jax.lax.scan(body, state, batches)

    return run


def make(cfg: FederatedConfig) -> FedOpt:
    from repro.core import agpdmm, fedavg, fedsplit, gpdmm, pdmm_graph, scaffold

    algos = {
        "gpdmm": gpdmm.make,
        "agpdmm": agpdmm.make,
        "scaffold": scaffold.make,
        "fedavg": fedavg.make,
        "fedsplit": fedsplit.make_inexact,
        # decentralized graph-PDMM (core.pdmm_graph over core.topology);
        # explicit names run the graph subsystem on ANY topology incl. star
        # (the conformance oracle), while plain "gpdmm" on a non-star
        # topology reroutes below
        "pdmm_graph": pdmm_graph.make_exact,
        "gpdmm_graph": pdmm_graph.make,
    }
    if cfg.algorithm not in algos:
        raise KeyError(f"unknown federated algorithm {cfg.algorithm!r}")
    if isinstance(cfg.eta, str):
        raise ValueError(
            "eta='auto' must be resolved host-side before the round is "
            "built: call core.autotune.resolve(cfg, grad_fn, params, m, "
            "batch) to derive the per-client stepsizes")
    if cfg.topology != "star" and cfg.algorithm not in ("pdmm_graph", "gpdmm_graph"):
        if cfg.algorithm == "gpdmm":
            # GPDMM over a general network IS graph-PDMM with the gradient
            # inner loop; route it rather than silently ignoring the topology
            return pdmm_graph.make(cfg)
        raise ValueError(
            f"algorithm {cfg.algorithm!r} has no decentralized analogue over "
            f"topology={cfg.topology!r}; use 'gpdmm' (rerouted to graph-PDMM), "
            f"'gpdmm_graph', or 'pdmm_graph'"
        )
    return algos[cfg.algorithm](cfg)
