"""Host-resident population store: O(cohort) device memory for cohort rounds.

The cohort engine (``use_cohort``, PR 5) already made COMPUTE scale with the
sampled cohort, but every resident ``(m, width)`` client buffer -- GPDMM's
``lam_s``/``x_c``/``u_hat``, SCAFFOLD's ``c_i``, FedAvg's ``u_hat`` -- still
lived in device memory, and the round tail still paid O(m) device work (the
scattered-buffer mean and the dense dual refresh).  At m = 10^6 LM-width
clients that is hundreds of GB of HBM for state the round never touches:
a cohort round READS AND WRITES only the sampled rows (the u_hat-cache
silence contract -- a silent client's resident state is round-invariant).

This module keeps the population in HOST numpy and stages only the cohort:

  * ``Runner.round`` gathers the sampled rows out of the host store, ships
    them with ``jax.device_put``, runs the algorithm's jitted device body
    (``<algo>.popstore_body`` -- identical per-row math to the device-arena
    cohort round), and scatters the returned rows back into the store.
    Peak device footprint is O(cohort x width) + the server row.

  * The participation draw is PURE in (seed, round) (``participation_key``),
    so round r+1's cohort is known DURING round r: a 2-slot prefetch ring
    host-gathers the next cohort's rows while the device crunches the
    current one, reconciles any rows the current round just updated
    (``np.intersect1d`` on the two index sets), and pre-stages the
    ``device_put`` so the next round starts without a host-side stall.

  * The O(m) server reads become O(cohort): a running ``sum(u_hat)`` is
    maintained incrementally in float64 with Kahan compensation
    (``sum' = sum - sum(old cohort rows) + sum(new cohort rows)``), which
    tracks the dense f32 mean at f32 resolution at any population size; the
    dense dual refresh is LAZY -- lam_{s|i} = rho (u_hat_i - x_s) is an
    elementwise function of the stored uplink cache, so the body
    reconstructs exactly the staged rows it needs (``ops.dual_from_uplink``)
    and no (m, width) dual buffer exists anywhere.

State layout (a plain dict pytree, so checkpointing/watchdog/``--resume``
work unchanged; the big host buffers stream chunk-wise through
``checkpoint.msgpack_ckpt``):

    {"x_s": pytree (device), "round": int,
     "pop": {name: np.ndarray (m, width)}, "pop_sum": np.float64 (width,),
     "pop_sum_comp": np.float64 (width,) [, "c": pytree (scaffold)]}

``Runner.round`` mutates the ``pop`` arrays IN PLACE (the scatter) and
returns a new dict sharing them -- callers must not hold the old state as a
snapshot (checkpoints serialise at save time, so the watchdog contract is
unaffected).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederatedConfig
from repro.core import arena, fedavg, gpdmm, scaffold
from repro.core import tree_util as T
from repro.core.api import resolved_rho, use_cohort
from repro.core.uplink import participation_key
from repro.telemetry import spans as _spans

_BODY_FACTORY = {
    "gpdmm": gpdmm.popstore_body,
    "agpdmm": partial(gpdmm.popstore_body, accel=True),
    "scaffold": scaffold.popstore_body,
    "fedavg": fedavg.popstore_body,
}

# Which resident (m, width) buffers each algorithm keeps in the host store,
# and which of them the server mean reads (None: the algorithm's server
# update is already O(cohort) on device -- SCAFFOLD -- and only a diagnostic
# reads the population sum).
POP_BUFFERS = {
    "gpdmm": ("u_hat", "x_c"),
    "agpdmm": ("u_hat",),
    "scaffold": ("c_i",),
    "fedavg": ("u_hat",),
}
MEAN_BUFFER = {"gpdmm": "u_hat", "agpdmm": "u_hat", "fedavg": "u_hat",
               "scaffold": None}

# Rows per chunk when (re)computing a full f64 column sum over a host
# buffer: bounds the transient f64 copy to chunk x width.
_SUM_CHUNK_ROWS = 4096


def supported(cfg: FederatedConfig) -> bool:
    return cfg.algorithm in POP_BUFFERS


def _col_sum64(buf: np.ndarray) -> np.ndarray:
    """Chunked float64 column sum: O(chunk x width) transient memory."""
    out = np.zeros(buf.shape[1], np.float64)
    for i in range(0, buf.shape[0], _SUM_CHUNK_ROWS):
        out += buf[i:i + _SUM_CHUNK_ROWS].astype(np.float64).sum(axis=0)
    return out


class _Staged:
    """One prefetch-ring slot: a round's cohort indices + staged rows."""
    __slots__ = ("round", "idx_np", "idx_dev", "host_rows", "dev_rows",
                 "store_ids")

    def __init__(self, round_idx, idx_np, idx_dev, host_rows, store_ids):
        self.round = round_idx
        self.idx_np = idx_np
        self.idx_dev = idx_dev
        self.host_rows = host_rows
        self.dev_rows = None
        self.store_ids = store_ids


class Runner:
    """Host-side driver for popstore rounds.  Mirrors the ``FedOpt``
    surface (``init`` / ``round`` / ``server_params``) but ``round`` is a
    HOST function -- it must NOT be wrapped in an outer ``jax.jit`` (the
    launchers dispatch on ``use_popstore`` and skip the jit)."""

    def __init__(self, cfg: FederatedConfig, grad_fn, *, per_step=False):
        if not supported(cfg):
            raise ValueError(
                f"popstore supports algorithms {sorted(POP_BUFFERS)}, "
                f"got {cfg.algorithm!r}")
        if cfg.algorithm == "scaffold" and cfg.uplink_bits is not None:
            scaffold.make(cfg)  # raises the canonical SCAFFOLD+EF21 error
        self.cfg = cfg
        self.grad_fn = grad_fn
        self.per_step = per_step
        self.algo = cfg.algorithm
        self.buffers = POP_BUFFERS[self.algo]
        self.mean_buffer = MEAN_BUFFER[self.algo]
        self._spec = None
        self._m = None
        self._body = None
        self._idx_fn = None
        self._next: Optional[_Staged] = None
        # prefetch-ring accounting, emitted as trace counter events when the
        # global tracer is on (docs/telemetry.md) -- a miss means the round
        # paid the host gather on the critical path
        self.ring_hits = 0
        self.ring_misses = 0

    # -- build ------------------------------------------------------------

    def _build(self, x_s, m: int):
        if self._body is not None and self._m == m:
            return
        cfg = self.cfg
        if not use_cohort(cfg, m):
            raise ValueError(
                "popstore rides the cohort engine: use_cohort(cfg, m) must "
                f"hold (participation={cfg.participation}, cohort="
                f"{cfg.cohort!r}, algorithm={cfg.algorithm!r}, m={m})")
        self._spec = arena.ArenaSpec.from_tree(x_s)
        self._m = m
        body = _BODY_FACTORY[self.algo](cfg, self._spec, m, self.grad_fn,
                                        self.per_step)
        # staged cohort rows are per-round temporaries: donate them so the
        # device-side footprint stays one cohort buffer per name, not two
        self._body = jax.jit(body, donate_argnums=(1,))
        self._idx_fn = jax.jit(
            lambda r: T.cohort_indices(participation_key(cfg, r), m,
                                       cfg.participation)[0])

    # -- staging / prefetch ring ------------------------------------------

    def _stage_host(self, round_idx: int, store) -> _Staged:
        idx_dev = self._idx_fn(jnp.int32(round_idx))
        idx_np = np.asarray(idx_dev)
        host_rows = {name: store[name][idx_np] for name in self.buffers}
        return _Staged(round_idx, idx_np, idx_dev, host_rows,
                       tuple(id(store[n]) for n in self.buffers))

    def _take_prefetch(self, round_idx: int, store) -> Optional[_Staged]:
        nxt, self._next = self._next, None
        if (nxt is not None and nxt.round == round_idx
                and nxt.store_ids == tuple(id(store[n])
                                           for n in self.buffers)):
            return nxt
        return None  # rollback / resume / fresh state: restage from scratch

    # -- state ------------------------------------------------------------

    def init(self, params, m: int):
        self._build(params, m)
        spec = self._spec
        row = np.asarray(spec.pack(params))
        pop = {}
        for name in self.buffers:
            buf = np.empty((m, spec.width), row.dtype)
            if name == "c_i":
                buf[:] = 0  # SCAFFOLD control variates start at zero
            else:
                buf[:] = row  # u_hat / x_c: round-0 broadcast of the server row
            pop[name] = buf
        sum_name = self.mean_buffer or self.buffers[0]
        if sum_name == "c_i":
            pop_sum = np.zeros(spec.width, np.float64)
        else:
            # m identical rows: m * row is the correctly rounded f64 sum
            pop_sum = row.astype(np.float64) * m
        state = {
            "x_s": params,
            "round": 0,
            "pop": pop,
            "pop_sum": pop_sum,
            "pop_sum_comp": np.zeros(spec.width, np.float64),
        }
        if self.algo == "scaffold":
            state["c"] = T.tree_zeros_like(params)
        self._next = None
        return state

    def _normalize(self, state):
        """Post-``--resume`` repair: checkpoint round-trips can hand back
        read-only numpy (frombuffer views), device arrays (small buffers
        below the streaming threshold load via ``jnp.asarray``), or f32
        sums (jnp would silently downcast f64 with x64 disabled).  The
        store must be writable host numpy and the running sum exact f64."""
        store = state["pop"]
        changed = False
        for name, buf in store.items():
            b = np.asarray(buf)
            if not isinstance(buf, np.ndarray) or not b.flags.writeable:
                b = np.array(b)
                changed = True
            store[name] = b
        s = np.asarray(state["pop_sum"])
        comp = np.asarray(state["pop_sum_comp"])
        sum_name = self.mean_buffer or self.buffers[0]
        if s.dtype != np.float64 or comp.dtype != np.float64:
            s = _col_sum64(store[sum_name])
            comp = np.zeros_like(s)
            changed = True
        state["pop_sum"], state["pop_sum_comp"] = s, comp
        if changed:
            self._next = None  # any prefetch staged off the old arrays is dead
        return state

    # -- the round ---------------------------------------------------------

    def round(self, state, batch):
        self._build(state["x_s"], next(iter(state["pop"].values())).shape[0])
        state = self._normalize(state)
        cfg, spec, m = self.cfg, self._spec, self._m
        r = int(state["round"])
        store = state["pop"]

        # telemetry (docs/telemetry.md): every phase below is a span on the
        # global tracer; all of it is the shared no-op singleton when
        # tracing is off, so the telemetry-off round does no added host work
        tr = _spans.get_tracer()
        staged = self._take_prefetch(r, store)
        if staged is None:
            # ring miss: the host gather lands on the critical path
            self.ring_misses += 1
            with tr.span("popstore/host_gather", {"round": r}):
                staged = self._stage_host(r, store)
        else:
            self.ring_hits += 1
        if tr.enabled:
            tr.counter("popstore/ring",
                       {"hit": self.ring_hits, "miss": self.ring_misses})
        if staged.dev_rows is None:
            with tr.span("popstore/h2d_stage", {"round": r}):
                staged.dev_rows = {k: jax.device_put(v)
                                   for k, v in staged.host_rows.items()}
        server = {"x_s": state["x_s"]}
        if self.algo == "scaffold":
            server["c"] = state["c"]
        # async dispatch: the device crunches while the host prefetches
        with tr.span("popstore/device_round", {"round": r}):
            rows_out, server_rows, dev_metrics = self._body(
                server, staged.dev_rows, staged.idx_dev, jnp.int32(r), batch)

        # prefetch ring: round r+1's cohort is already determined, so gather
        # its rows NOW, overlapping the device compute above.  Rows round r
        # is about to update are reconciled below, after the scatter.
        with tr.span("popstore/prefetch_gather", {"round": r + 1}):
            nxt = self._stage_host(r + 1, store)

        with tr.span("popstore/device_sync", {"round": r}):
            new_rows = {k: np.asarray(v) for k, v in rows_out.items()}  # sync
        idx_np = staged.idx_np

        with tr.span("popstore/scatter_back", {"round": r}):
            # incremental server sum BEFORE the scatter (needs the old rows)
            sum_name = self.mean_buffer or self.buffers[0]
            delta = (new_rows[sum_name].astype(np.float64).sum(axis=0)
                     - store[sum_name][idx_np].astype(np.float64).sum(axis=0))
            # Kahan-compensated accumulation: the per-round delta is tiny next
            # to the population sum at large m, exactly where naive f64 += leaks
            y = delta - state["pop_sum_comp"]
            t = state["pop_sum"] + y
            comp_new = (t - state["pop_sum"]) - y
            sum_new = t

            for name in self.buffers:
                store[name][idx_np] = new_rows[name]

            # reconcile the prefetched slot with the rows just scattered
            common, pos_next, _ = np.intersect1d(nxt.idx_np, idx_np,
                                                 return_indices=True)
            if common.size:
                for name, buf in nxt.host_rows.items():
                    buf[pos_next] = store[name][common]
        with tr.span("popstore/h2d_stage", {"round": r + 1, "prefetch": True}):
            nxt.dev_rows = {k: jax.device_put(v)
                            for k, v in nxt.host_rows.items()}
        self._next = nxt

        new_state = {
            "round": r + 1,
            "pop": store,
            "pop_sum": sum_new,
            "pop_sum_comp": comp_new,
        }
        host_metrics = {"used_popstore": np.float32(1.0)}
        if self.algo == "scaffold":
            new_state["x_s"] = spec.unpack(server_rows["x_s"])
            new_state["c"] = spec.unpack(server_rows["c"])
            c_row64 = np.asarray(server_rows["c"]).astype(np.float64)
            host_metrics["c_sum_norm"] = np.float32(
                np.linalg.norm(sum_new - m * c_row64))
        else:
            # the round's single "all-reduce": the incrementally maintained
            # population sum, read at f32 resolution
            x_row = jnp.asarray((sum_new / m).astype(np.float32))
            new_state["x_s"] = spec.unpack(x_row)
            if self.algo in ("gpdmm", "agpdmm"):
                rho = resolved_rho(cfg)
                # KKT invariant (25) off the lazy dual: sum_i lam_{s|i}
                # = rho (sum_i u_hat_i - m x_s)
                host_metrics["lam_sum_norm"] = np.float32(np.linalg.norm(
                    rho * (sum_new
                           - m * np.asarray(x_row).astype(np.float64))))
        return new_state, dict(dev_metrics) | host_metrics

    def server_params(self, state):
        return state["x_s"]


def device_bytes(cfg: FederatedConfig, width: int, m: int) -> int:
    """Staged-state device footprint bound for one popstore round: the
    2-slot ring of cohort rows per resident buffer, plus the body's own
    cohort-sized temporaries are accounted by callers.  Benchmarks report
    this next to the O(m x width) arena-resident footprint it replaces."""
    mc = T.cohort_count(m, cfg.participation)
    n_buf = len(POP_BUFFERS[cfg.algorithm])
    return 2 * n_buf * mc * width * 4
