"""Federated LM training launcher (runs for real on whatever devices exist).

    PYTHONPATH=src python -m repro.launch.train \
        --arch olmo-1b --reduced --steps 50 --algorithm gpdmm --k 4

``--reduced`` (the default) shrinks widths for CPU runs; ``--full`` keeps
the published widths, and ``--layers N`` then cuts only the depth -- the
cut that fits a model's published widths on one chip (``chip_smoke.py``
runs OLMo-1B this way on a TPU v5e).

Checkpointing: ``--ckpt-dir`` saves the FULL federated state (every arena
buffer, the server pytree, and the round counter) at the end of the run;
``--resume`` restores the latest checkpoint and continues the SAME
trajectory -- the synthetic data stream is re-keyed from the restored round
counter, so save-at-r + resume equals the uninterrupted run at f32
(tests/test_cohort.py pins this).  Partial-participation runs on the cohort
engine (``core.api.use_cohort``) feed cohort-sized batches from
``data.synthetic.cohort_lm_batches`` -- data is generated only for the
clients that actually fire each round.

Robustness (docs/robustness.md): ``--faults`` injects a deterministic fault
schedule (``core.faults``), ``--screen`` gates the fused uplink screen, and
``--watchdog`` arms a divergence watchdog -- after ``--watchdog-patience``
consecutive bad logged rows (non-finite metrics, or server loss above
``--watchdog-factor`` x the attempt's best) it rolls the full federated
state back to the newest healthy checkpoint anchor and retries with the
stepsize scaled by ``--eta-backoff``.  The fault trace is a pure function
of (fault seed, round, client), so replayed rounds replay identical faults:
screening remedies corruption, the watchdog remedies stepsize divergence.

Telemetry (docs/telemetry.md): ``--telemetry`` turns on the metrics
registry (fault/rollback counters, loss/residual gauges) with a structured
end-of-run summary; ``--trace-out trace.json`` additionally records
round-phase spans (batch build / dispatch / block_until_ready / eval+log /
checkpoint save+load, plus the popstore staging phases and watchdog
strike/rollback instants) as Perfetto-loadable Chrome trace JSON;
``--metrics-out metrics.jsonl`` streams every logged history row through
the crash-safe JSONL sink as it happens, so loss curves survive a crash
instead of living only in stdout; ``--profile-rounds A:B`` captures a
``jax.profiler`` device trace for exactly those rounds, with the round
spans on the trace's own clock (``telemetry.spans``).  The summary counts
the run's XLA compilations (``jit/compiles``, ``jit/compile_s``).  All of it
is off by default, and the off path adds no per-round host work (the
dispatch wrappers are only installed when tracing or profiling is on).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro import telemetry as tel
from repro.configs import get_arch
from repro.configs.base import FaultConfig, FederatedConfig
from repro.core import make as make_fed
from repro.core import make_scan_rounds, popstore
from repro.core.api import FedOpt, use_arena, use_cohort, use_popstore
from repro.data.synthetic import cohort_lm_batches, lm_batches
from repro.launch import compile_cache
from repro.models import build as build_model


class History(list):
    """The logged round rows ``run`` returns; ``state`` is the run's final
    federated state (still on the device), for callers that check it."""

    state = None


def run(
    arch: str,
    *,
    reduced: bool = True,
    layers: int | None = None,
    steps: int = 20,
    algorithm: str = "gpdmm",
    k: int = 2,
    eta: float | str = 0.3,
    tol: float = 0.0,
    patience: int = 1,
    m: int = 4,
    per_client_batch: int = 4,
    seq_len: int = 128,
    seed: int = 0,
    ckpt_dir: str | None = None,
    resume: bool = False,
    log_every: int = 5,
    uplink_bits: int | None = None,
    participation: float = 1.0,
    popstore_mode: bool | str = "auto",
    rounds_per_call: int = 1,
    faults: str | FaultConfig | None = None,
    screen: bool | str = "auto",
    deadline: float = math.inf,
    max_staleness: int = 0,
    stale_gamma: float = 0.5,
    async_rounds: bool | str = "auto",
    watchdog: bool = False,
    watchdog_factor: float = 10.0,
    watchdog_patience: int = 2,
    eta_backoff: float = 0.5,
    max_rollbacks: int = 3,
    ckpt_every: int = 0,
    ckpt_keep: int = 3,
    expect_demotions: int = 0,
    expect_rollbacks: int = 0,
    telemetry: bool = False,
    trace_out: str | None = None,
    metrics_out: str | None = None,
    profile_rounds: str | None = None,
    profile_dir: str | None = None,
):
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)  # depth only
    fault_cfg = FaultConfig.parse(faults) if isinstance(faults, str) else faults
    if watchdog and not ckpt_dir:
        raise ValueError("--watchdog needs --ckpt-dir (rollback anchors)")

    # telemetry: any output flag implies the master switch; the tracer only
    # records when it has a sink (spans without a file are dead weight).
    # The GLOBAL tracer is configured so the instrumented library paths
    # (core.popstore staging, serve's watcher) emit into the same trace.
    tel_on = (telemetry or bool(trace_out) or bool(metrics_out)
              or bool(profile_rounds))
    tracer = tel.get_tracer()
    was_tracing = tracer.enabled
    if trace_out:
        tracer.configure(enabled=True, trace_out=trace_out)
    registry = tel.Registry() if tel_on else None
    sink = tel.JsonlSink(metrics_out) if metrics_out else None
    prof = tel.RoundProfiler.parse(
        profile_rounds,
        profile_dir or (str(pathlib.Path(trace_out).parent / "jaxprof")
                        if trace_out else "telemetry/jaxprof"))
    # the profiler window turns the tracer on inside it, so the span
    # wrappers go in whenever either can record
    spans_on = tracer.enabled or prof is not None
    if tel_on:
        tel.compiles.install()
    compiles0 = tel.compiles.totals()

    model = build_model(cfg)  # the model ignores cfg.fed (checked)

    key = jax.random.key(seed)
    params = model.init(key)

    _eta_cache: list = []

    def resolved_eta():
        """The CLI eta, with ``"auto"`` resolved ONCE host-side into the
        per-client tuple (power-iteration L_i estimates at the init params
        over a fixed probe batch, ``core.autotune``).  Cached: every rebuild
        -- including each watchdog backoff -- reuses the same derived
        values, and the checkpoint fingerprint records the CLI value, so a
        ``--resume`` re-derives the identical tuple deterministically."""
        if not isinstance(eta, str):
            return eta
        if not _eta_cache:
            from repro.core import autotune
            probe = next(lm_batches(jax.random.key(seed + 3), 1, m,
                                    per_client_batch, seq_len, cfg.vocab_size))
            gf = lambda p, b: jax.grad(lambda q: model.loss(q, b)[0])(p)
            L = autotune.estimate_L(gf, params, m, probe)
            etas = autotune.derive_eta(L)
            print(f"[train] auto-eta: per-client L in [{L.min():.4g}, "
                  f"{L.max():.4g}], eta in [{etas.min():.4g}, "
                  f"{etas.max():.4g}]", flush=True)
            _eta_cache.append(tuple(float(e) for e in etas))
        return _eta_cache[0]

    def fed_cfg(scale: float) -> FederatedConfig:
        # eta backoff after a rollback re-derives rho = 1/(K eta') too: the
        # watchdog shrinks the stepsize of the whole primal-dual pair (under
        # auto-eta the backoff rescales every per-client entry uniformly)
        from repro.core import autotune
        fc = dataclasses.replace(
            cfg.fed, algorithm=algorithm, inner_steps=k, eta=resolved_eta(),
            num_clients=m, layout="client_axis", uplink_bits=uplink_bits,
            participation=participation, popstore=popstore_mode,
            rounds_per_call=rounds_per_call,
            faults=fault_cfg, screen=screen, async_rounds=async_rounds,
            deadline=deadline, max_staleness=max_staleness,
            stale_gamma=stale_gamma, tol=tol, patience=patience,
        )
        return autotune.scale_eta(fc, scale)

    cfg = dataclasses.replace(cfg, fed=fed_cfg(1.0))

    # fingerprint saved with every checkpoint and checked on --resume: a
    # restored state only continues the SAME trajectory if the run that
    # wrote it used the same optimiser/data hyper-parameters
    run_config = {
        "arch": arch, "reduced": reduced, "algorithm": algorithm, "k": k,
        "eta": eta, "m": m, "per_client_batch": per_client_batch,
        "seq_len": seq_len, "seed": seed, "uplink_bits": uplink_bits,
        "participation": participation,
    }
    if layers is not None:
        # joins the fingerprint only when set, so older checkpoints resume
        run_config["layers"] = layers
    if fault_cfg is not None:
        # the seeded fault trace is part of the trajectory, so it joins the
        # fingerprint -- but only when a schedule is active, so checkpoints
        # written before this launcher grew fault support still resume
        run_config["faults"] = dataclasses.asdict(fault_cfg)
        run_config["screen"] = screen if isinstance(screen, str) else bool(screen)
        from repro.core import faults as faults_mod

        if faults_mod.async_on(cfg.fed):
            # the staleness knobs reshape the trajectory (admission weights,
            # deadline demotions), so they join the fingerprint -- but only
            # when the async engine is actually on, so pre-ISSUE-7
            # checkpoints (and delay-as-silence runs) still resume
            run_config["deadline"] = deadline
            run_config["max_staleness"] = max_staleness
            run_config["stale_gamma"] = stale_gamma

    # cohort engine active -> feed cohort-sized batches (rows = the round's
    # active clients, sorted by id) so data is never generated for silent
    # clients; popstore additionally moves the resident (m, width) client
    # buffers to a HOST store and stages only the sampled cohort per round
    # (core.popstore), making device memory O(cohort)
    cohort = use_cohort(cfg.fed, m) and use_arena(cfg.fed, params)
    pop_on = cohort and use_popstore(cfg.fed, m)
    if pop_on:
        # the store changes the checkpointed state LAYOUT (host buffers +
        # running sums instead of device arenas), so it joins the resume
        # fingerprint -- but only when on, so older checkpoints still resume
        run_config["popstore"] = True

    def load_latest_good(what: str):
        """Newest LOADABLE checkpoint under ckpt_dir: a truncated or corrupt
        file at the newest step (a crash mid-copy, a bad disk) is skipped
        with a loud warning instead of killing the run -- resume and
        watchdog rollback both degrade to the last good anchor."""
        for step_n in sorted(ckpt.steps(ckpt_dir), reverse=True):
            try:
                with tracer.span("ckpt/load", {"step": step_n}):
                    return step_n, ckpt.load(ckpt_dir, step_n)
            except ValueError as e:
                print(f"[train] {what}: SKIPPING unreadable checkpoint step "
                      f"{step_n}: {e}", flush=True)
        raise FileNotFoundError(
            f"{what}: no loadable checkpoint under {ckpt_dir}")

    start = 0
    eta_scale = 1.0
    state = None
    if resume:
        if not ckpt_dir:
            raise ValueError("--resume needs --ckpt-dir")
        last, payload = load_latest_good("--resume")
        if "fed_state" not in payload:
            raise ValueError(
                f"checkpoint step {last} under {ckpt_dir} has no 'fed_state' "
                "(written by a pre-ISSUE-5 launcher that saved only server "
                "params); it cannot resume a trajectory -- retrain, or load "
                "payload['server'] manually for serving")
        saved_cfg = payload.get("config", {})
        diffs = {kk: (saved_cfg.get(kk), vv) for kk, vv in run_config.items()
                 if saved_cfg.get(kk) != vv}
        if diffs:
            raise ValueError(
                f"--resume config mismatch vs checkpoint (saved, requested): "
                f"{diffs}; resuming would NOT continue the same trajectory")
        if bool(saved_cfg.get("popstore", False)) != pop_on:
            # popstore state (host store + running sums) and arena state
            # (device buffers) are different LAYOUTS of the same trajectory;
            # the round drivers cannot consume each other's checkpoints
            raise ValueError(
                f"--resume popstore mismatch: checkpoint was written with "
                f"popstore={bool(saved_cfg.get('popstore', False))}, this "
                f"run resolves popstore={pop_on} (popstore_mode="
                f"{popstore_mode!r}); pass --popstore on/off to match")
        # the FULL federated state (arena buffers + server pytree + round
        # counter) resumes; the data stream re-keys from the round counter,
        # so the continuation is the uninterrupted trajectory.  fed.init is
        # skipped entirely -- at population scale the (m, width) arena
        # buffers it would broadcast just to be overwritten are the bulk of
        # the job's memory
        state = payload["fed_state"]
        start = int(payload["round"])
        # a watchdog-backed-off run resumes at its backed-off stepsize; the
        # scale rides outside the fingerprint (it IS the same trajectory,
        # continued at the eta the rollback settled on)
        eta_scale = float(payload.get("eta_scale", 1.0))
        print(f"[train] resumed full fed state at round {start} from {ckpt_dir}"
              + (f" (eta_scale={eta_scale:g})" if eta_scale != 1.0 else ""))
    if start >= steps:
        print(f"[train] checkpoint already at round {start} >= steps {steps}; "
              f"nothing to do")
        return []

    def client_grad(p, b):
        return jax.grad(lambda q: model.loss(q, b)[0])(p)

    # donate the round state: the arena/round update aliases its input
    # buffers in place instead of holding two copies of the (m, params) state.
    # With rounds_per_call > 1 the scan driver runs R full rounds per
    # dispatch over a leading-R batch stack (metrics come back stacked).
    R = max(1, rounds_per_call)
    if pop_on and R > 1:
        # the popstore round is a HOST driver (gather/scatter against host
        # numpy + the prefetch ring): it cannot run under lax.scan
        print(f"[train] popstore active: forcing rounds_per_call "
              f"{rounds_per_call} -> 1 (host-side round driver)")
        R = 1

    def _instrument(fn):
        """Dispatch/sync spans around a round function.  Installed ONLY when
        tracing or profiling is on: the telemetry-off path keeps the
        original callable (and its async-dispatch overlap) with zero added
        per-round host work.  The explicit block_until_ready span is what
        splits "enqueue the round" from "wait for the device" in the trace;
        outside a profiler window with no ``--trace-out`` the wrapper
        passes straight through."""
        if not spans_on:
            return fn

        def wrapped(s, b):
            if not tracer.enabled:
                return fn(s, b)
            with tracer.span("round/dispatch"):
                out = fn(s, b)
            with tracer.span("round/block_until_ready"):
                jax.block_until_ready(out)
            return out

        return wrapped

    def build(scale: float):
        """(fed, step_fn, round_fn) at the given eta scale -- rebuilt after
        every watchdog backoff so the jitted round sees the new stepsize."""
        if pop_on:
            runner = popstore.Runner(fed_cfg(scale), client_grad)
            # the FedOpt surface the rest of the launcher speaks, but
            # round_fn is a HOST function -- no outer jit, no donation (the
            # runner mutates its host store in place instead)
            fed = FedOpt(name=algorithm, init=runner.init,
                         round=runner.round,
                         server_params=runner.server_params)
            rf = _instrument(runner.round)
            return fed, rf, rf
        fed = make_fed(fed_cfg(scale))

        def one_round(s, b):
            s2, mets = fed.round(s, client_grad, b)
            if tol > 0.0:  # static gate: tol=0 compiles the pre-PR graph
                from repro.core import autotune
                mets = {**mets, **autotune.state_residual(s, s2)}
            return s2, mets

        round_fn = jax.jit(one_round, donate_argnums=(0,))
        if R > 1:
            scan_rounds = make_scan_rounds(fed, client_grad, tol=tol)
            step_fn = jax.jit(lambda s, b: scan_rounds(s, b),
                              donate_argnums=(0,))
        else:
            step_fn = round_fn
        return fed, _instrument(step_fn), _instrument(round_fn)

    @jax.jit
    def eval_loss(params, batch):
        # server-model loss averaged over the same stacked batch
        losses = jax.vmap(lambda b: model.loss(params, b)[0])(batch)
        return losses.mean()

    history = History()
    n_rounds = steps - start

    def make_data(from_round: int):
        # re-keyed from the starting round: a rollback (or --resume)
        # regenerates the identical per-round stream the uninterrupted run
        # would have seen from that round on
        data_key = jax.random.key(seed + 1)
        if cohort:
            return cohort_lm_batches(
                data_key, steps - from_round, m, per_client_batch, seq_len,
                cfg.vocab_size, participation=participation,
                fed_seed=cfg.fed.seed, start=from_round,
            )
        return lm_batches(data_key, steps - from_round, m, per_client_batch,
                          seq_len, cfg.vocab_size, start=from_round)

    # cohort batches only cover the round's active clients, so evaluating
    # the server loss on them would track the cohort's topics, not the
    # population objective (incomparable across participation settings):
    # hold out ONE fixed full-population batch for the logged loss instead
    eval_batch = None
    if cohort:
        eval_batch = next(lm_batches(jax.random.key(seed + 2), 1, m,
                                     per_client_batch, seq_len, cfg.vocab_size))

    def metrics_row(metrics):
        # last-round values, whether stacked (R,) from the scan or scalars
        return {kk: float(jnp.asarray(v).reshape(-1)[-1])
                for kk, v in metrics.items() if kk != "trace"}

    class _Watchdog:
        """Trips after ``watchdog_patience`` consecutive bad logged rows; a
        row is bad when any metric is non-finite or the server loss exceeds
        ``watchdog_factor`` x this attempt's best loss."""

        def __init__(self):
            self.best = math.inf
            self.strikes = 0

        def note(self, row) -> bool:
            bad = (any(not math.isfinite(v) for v in row.values()
                       if isinstance(v, float))
                   or row["server_loss"] > watchdog_factor * self.best)
            if bad:
                self.strikes += 1
                tracer.instant("watchdog/strike",
                               {"round": row["round"],
                                "strikes": self.strikes,
                                "server_loss": row["server_loss"]})
                if registry is not None:
                    registry.counter("watchdog_strikes").inc()
            else:
                self.strikes = 0
                self.best = min(self.best, row["server_loss"])
            return self.strikes >= watchdog_patience

    injected_total = demoted_total = 0.0
    last_saved = None

    def note_faults(metrics):
        # fault counters sum over every executed dispatch (stacked (R,) rows
        # from the scan included), so the end-of-run summary covers rounds a
        # rollback later replayed too
        nonlocal injected_total, demoted_total
        if metrics and "faults_demoted" in metrics:
            injected_total += float(jnp.sum(jnp.asarray(metrics["faults_injected"])))
            demoted_total += float(jnp.sum(jnp.asarray(metrics["faults_demoted"])))
        if registry is not None and metrics:
            # counter-semantic device metrics sum over EVERY dispatch, so
            # the registry totals match the launcher's own accounting (the
            # --expect-demotions gate) exactly -- logged rows alone would
            # miss unlogged rounds and all but the last stacked scan row
            for key in tel.COUNTER_KEYS:
                if key in metrics:
                    v = float(jnp.sum(jnp.asarray(metrics[key])))
                    if math.isfinite(v):
                        registry.counter(key).inc(v)

    def save_anchor(fed, state, scale):
        done = int(state["round"])
        with tracer.span("ckpt/save", {"round": done}):
            t0 = time.perf_counter()
            path = ckpt.save(ckpt_dir, done, {
                "server": fed.server_params(state),
                "fed_state": state,
                "round": done,
                "config": run_config,
                "eta_scale": scale,
            }, keep=ckpt_keep)
            dt = time.perf_counter() - t0
        if registry is not None:
            registry.counter("ckpt_saves").inc()
            registry.counter("ckpt_bytes").inc(os.path.getsize(path))
            registry.histogram("ckpt_save_s").observe(dt)
        return done

    def traced_batches(it):
        """Wrap the batch stream so each ``next`` is a round/batch_build
        span.  Only installed when tracing or profiling -- the off path
        iterates the original generator untouched."""
        if not spans_on:
            return it

        def gen():
            src = iter(it)
            while True:
                with tracer.span("round/batch_build"):
                    try:
                        b = next(src)
                    except StopIteration:
                        return
                yield b

        return gen()

    def attempt(fed, step_fn, round_fn, state, from_round, scale, wd):
        """One trajectory attempt from ``from_round``; returns
        ``(state, "done" | "diverged")``."""
        nonlocal last_saved
        data = traced_batches(make_data(from_round))

        ee = None
        if tol > 0.0:
            from repro.core import autotune
            ee = autotune.EarlyExit(tol, patience)

        def note_exit(i):
            saved = steps - i
            tracer.instant("autotune/early_exit",
                           {"round": i, "rounds_saved": saved,
                            "rel_residual": ee.last_rel})
            if registry is not None:
                registry.counter("rounds_saved").inc(saved)
            print(f"[train] early exit at round {i}: relative residual "
                  f"{ee.last_rel:.3g} < tol {tol:g} for {patience} "
                  f"consecutive round(s); {saved} budgeted round(s) saved",
                  flush=True)

        def log_round(i, state, metrics, eb):
            nonlocal last_saved
            with tracer.span("round/eval_log", {"round": i}):
                row = {"round": i,
                       "server_loss": float(eval_loss(fed.server_params(state), eb)),
                       **(metrics_row(metrics) if metrics is not None else {})}
            history.append(row)
            if sink is not None:
                # incremental: each logged row is flushed as it happens, so
                # the loss curve survives a crash (read_jsonl tolerates the
                # torn final line a mid-write kill leaves)
                sink.write({"kind": "round", **row})
            if registry is not None:
                # counters=(): logged rows carry LAST-dispatch values, so
                # they feed gauges/histograms only; the exact counter totals
                # come from note_faults, which sees every executed dispatch
                # (stacked scan rows and unlogged rounds included)
                registry.absorb(row, counters=())
            tracer.flush()
            print(f"[train] {json.dumps(row)}", flush=True)
            diverged = wd.note(row) if wd is not None else False
            healthy = (math.isfinite(row["server_loss"])
                       and (wd is None or wd.strikes == 0))
            if (ckpt_dir and ckpt_every > 0 and healthy
                    and (last_saved is None or i - last_saved >= ckpt_every)):
                save_anchor(fed, state, scale)
                last_saved = i
            return diverged

        if R > 1:
            # tail shorter than R (steps % R != 0) falls back to jitted,
            # donated per-round dispatches -- same step semantics, no eager
            # path
            pending = []
            i = from_round
            last = metrics = None
            for batch in data:
                pending.append(batch)
                last = batch
                if len(pending) < R:
                    continue
                with tracer.span("round/batch_stack", {"R": R}):
                    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pending)
                pending = []
                if prof is not None:
                    # the scan dispatch is all-or-nothing: capture covers
                    # every R-round block intersecting the window
                    prof.before_round(i + 1)
                state, metrics = step_fn(state, stacked)  # metrics stacked (R,)
                note_faults(metrics)
                i += R
                if prof is not None:
                    jax.block_until_ready(state)
                    prof.after_round(i)
                if ee is not None and "res_dx2" in metrics:
                    # the scan chunk is all-or-nothing: the criterion may
                    # have fired mid-chunk, but the state already carries the
                    # whole chunk -- only the UNDISPATCHED rounds are saved
                    if ee.update(metrics["res_dx2"], metrics["res_x2"]) is not None:
                        note_exit(i)
                        eb = eval_batch if eval_batch is not None else last
                        if not history or history[-1]["round"] != i:
                            log_round(i, state, metrics, eb)
                        return state, "done"
                if (i - R) // max(1, log_every) != i // max(1, log_every):
                    eb = eval_batch if eval_batch is not None else last
                    if log_round(i, state, metrics, eb):
                        return state, "diverged"
            for batch in pending:
                state, metrics = round_fn(state, batch)
                note_faults(metrics)
                i += 1
            if last is not None and (not history or history[-1]["round"] != i):
                # always log the FINAL state (the R=1 path's i == steps-1 row)
                eb = eval_batch if eval_batch is not None else last
                if log_round(i, state, metrics, eb):
                    return state, "diverged"
            return state, "done"

        # ``i`` counts COMPLETED rounds after each dispatch (== the state's
        # round counter), the same numbering the R>1 scan path logs -- loss
        # curves from the two drivers line up row-for-row, and the guarded
        # ``max(1, log_every)`` matches it too (--log-every 0 used to
        # ZeroDivisionError here while the scan path survived)
        for i, batch in enumerate(data, start=from_round + 1):
            if prof is not None:
                prof.before_round(i)
            state, metrics = step_fn(state, batch)
            if prof is not None:
                # the capture window must hold COMPLETE rounds: force the
                # async dispatch to finish before deciding to stop
                jax.block_until_ready(state)
                prof.after_round(i)
            note_faults(metrics)
            if ee is not None and metrics and "res_dx2" in metrics:
                if ee.update(metrics["res_dx2"], metrics["res_x2"]) is not None:
                    note_exit(i)
                    eb = eval_batch if eval_batch is not None else batch
                    if not history or history[-1]["round"] != i:
                        log_round(i, state, metrics, eb)
                    return state, "done"
            if (i - 1) // max(1, log_every) != i // max(1, log_every) or i == steps:
                eb = eval_batch if eval_batch is not None else batch
                if log_round(i, state, metrics, eb):
                    return state, "diverged"
        return state, "done"

    t0 = time.perf_counter()
    rollbacks = 0
    wd = _Watchdog() if watchdog else None
    fed, step_fn, round_fn = build(eta_scale)
    if state is None:
        state = fed.init(params, m)
    if wd is not None and ckpt.latest_step(ckpt_dir) is None:
        # round-start anchor: the very first divergence has somewhere to
        # roll back to
        last_saved = save_anchor(fed, state, eta_scale)
    try:
        while True:
            state, status = attempt(fed, step_fn, round_fn, state, start,
                                    eta_scale, wd)
            if status == "done":
                break
            rollbacks += 1
            if rollbacks > max_rollbacks:
                raise RuntimeError(
                    f"divergence watchdog: {rollbacks} rollbacks exceeded "
                    f"max_rollbacks={max_rollbacks} (eta_scale={eta_scale:g}); "
                    f"the run does not converge at any tried stepsize")
            _anchor, payload = load_latest_good("watchdog rollback")
            state = payload["fed_state"]
            start = int(payload["round"])
            eta_scale *= eta_backoff
            wd = _Watchdog()
            tracer.instant("watchdog/rollback",
                           {"to_round": start, "eta_scale": eta_scale,
                            "rollbacks": rollbacks})
            if registry is not None:
                registry.counter("rollbacks").inc()
            print(f"[train] watchdog: diverged; rolled back to round {start}, "
                  f"eta_scale -> {eta_scale:g}", flush=True)
            fed, step_fn, round_fn = build(eta_scale)
        dt = time.perf_counter() - t0
        print(f"[train] {n_rounds} rounds (K={k}, m={m}) in {dt:.1f}s; algo={algorithm}, "
              f"rounds_per_call={R}" + (", cohort batches" if cohort else ""))

        if ckpt_dir:
            # the FULL fed state (arena buffers, server pytree, round counter),
            # not just server params: `load` + --resume continues the exact
            # trajectory.  "server" stays for serve-side consumers.
            done = int(state["round"])
            save_anchor(fed, state, eta_scale)
            # retention applies to the final save too, not just the periodic
            # anchors -- a finished run keeps exactly ckpt_keep
            print(f"[train] full-state checkpoint (round {done}) saved to {ckpt_dir}")
        if fault_cfg is not None or watchdog:
            print(f"[train] robustness: faults_injected={injected_total:.0f} "
                  f"demoted={demoted_total:.0f} rollbacks={rollbacks} "
                  f"eta_scale={eta_scale:g}")
    finally:
        # telemetry teardown runs on the crash path too: every flushed span
        # and JSONL row survives, and the summary row records the totals up
        # to the failure (the sinks are exactly for post-mortems)
        if prof is not None:
            prof.close()
        if registry is not None:
            registry.gauge("eta_scale").set(eta_scale)
            n, secs = tel.compiles.totals()
            registry.counter("jit/compiles").inc(n - compiles0[0])
            registry.counter("jit/compile_s").inc(secs - compiles0[1])
        if sink is not None:
            sink.write({"kind": "summary", **registry.summary_row()})
            sink.close()
        if tel_on:
            print(f"[train] telemetry: "
                  f"{json.dumps(registry.summary_row(), default=float)}",
                  flush=True)
        if trace_out:
            trace_path = tracer.close()
            if trace_path:
                print(f"[train] trace written to {trace_path} "
                      f"(load in https://ui.perfetto.dev)", flush=True)
            tracer.configure(enabled=was_tracing)
    if expect_demotions and demoted_total < expect_demotions:
        raise RuntimeError(
            f"expected >= {expect_demotions} screened demotions, "
            f"saw {demoted_total:.0f}")
    if expect_rollbacks and rollbacks < expect_rollbacks:
        raise RuntimeError(
            f"expected >= {expect_rollbacks} watchdog rollbacks, "
            f"saw {rollbacks}")
    history.state = state
    return history


def _eta_arg(s: str):
    """``--eta`` accepts a float or the literal ``auto``."""
    return "auto" if s == "auto" else float(s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to N layers, keeping its widths")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--algorithm", default="gpdmm",
                    choices=["gpdmm", "agpdmm", "scaffold", "fedavg", "fedsplit"])
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--eta", type=_eta_arg, default=0.3,
                    help="client stepsize, or 'auto' to derive per-client "
                         "eta_i = safety / L_i from a power-iteration "
                         "curvature probe (see docs/autotune.md)")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="relative fixed-point residual tolerance: terminate "
                         "once ||x - x_prev|| / ||x|| < tol for --patience "
                         "consecutive rounds (0 = fixed round budget)")
    ap.add_argument("--patience", type=int, default=1,
                    help="consecutive sub-tol rounds required before the "
                         "early exit fires")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest full-state checkpoint from "
                         "--ckpt-dir and continue the same trajectory")
    ap.add_argument("--uplink-bits", type=int, default=None,
                    help="EF21 delta-quantised uplink (beyond paper)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients active per round (async PDMM; "
                         "< 1 runs the cohort-sampled round engine)")
    ap.add_argument("--popstore", default="auto", choices=["auto", "on", "off"],
                    help="host-resident population store: O(cohort) device "
                         "memory with prefetch-overlapped staging (auto = on "
                         "for cohort runs at >= popstore_min_clients)")
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="rounds per jitted dispatch (lax.scan round batching)")
    ap.add_argument("--log-every", type=int, default=5,
                    help="rounds between logged rows (the watchdog and the "
                         "periodic anchors act at logged rows)")
    ap.add_argument("--faults", default=None,
                    help="deterministic fault schedule, e.g. "
                         "'dropout=0.1,corrupt=0.05,seed=7' -- pure in "
                         "(seed, round, client), so the trace replays exactly")
    ap.add_argument("--screen", default="auto", choices=["auto", "on", "off"],
                    help="fused uplink screening (auto = on iff faults active)")
    ap.add_argument("--deadline", type=float, default=math.inf,
                    help="straggler deadline in rounds: a drawn lateness past "
                         "it demotes the client to silence for the round")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="admit stale uplinks up to this age (0 = the "
                         "synchronous point: delayed uplinks never land)")
    ap.add_argument("--stale-gamma", type=float, default=0.5,
                    help="admission weight gamma**age for arriving stale rows")
    ap.add_argument("--async", dest="async_rounds", default="auto",
                    choices=["auto", "on", "off"],
                    help="bounded-staleness round engine (auto = on iff the "
                         "staleness knobs deviate from the synchronous point)")
    ap.add_argument("--watchdog", action="store_true",
                    help="divergence watchdog: roll back to the newest healthy "
                         "checkpoint with eta backoff (needs --ckpt-dir)")
    ap.add_argument("--watchdog-factor", type=float, default=10.0,
                    help="a logged loss above factor x best counts as bad")
    ap.add_argument("--watchdog-patience", type=int, default=2,
                    help="consecutive bad logged rows before rollback")
    ap.add_argument("--eta-backoff", type=float, default=0.5,
                    help="eta multiplier applied on each rollback")
    ap.add_argument("--max-rollbacks", type=int, default=3)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a rollback anchor every N logged rounds "
                         "(0 = final checkpoint only)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retain only the newest N anchors")
    ap.add_argument("--expect-demotions", type=int, default=0,
                    help="fail unless >= N uplinks were demoted (chaos CI gate)")
    ap.add_argument("--expect-rollbacks", type=int, default=0,
                    help="fail unless >= N rollbacks happened (chaos CI gate)")
    ap.add_argument("--telemetry", action="store_true",
                    help="metrics registry + structured end-of-run summary "
                         "(implied by any of the output flags below)")
    ap.add_argument("--trace-out", default=None,
                    help="write round-phase spans as Chrome trace-event JSON "
                         "(open in Perfetto); enables the span tracer")
    ap.add_argument("--metrics-out", default=None,
                    help="stream every logged history row + an end-of-run "
                         "summary to this JSONL file (crash-safe, one flush "
                         "per row)")
    ap.add_argument("--profile-rounds", default=None,
                    help="capture a jax.profiler device trace for exactly "
                         "rounds A:B (e.g. '3:5'; see docs/telemetry.md)")
    ap.add_argument("--profile-dir", default=None,
                    help="jax.profiler output dir (default: next to "
                         "--trace-out, else ./telemetry/jaxprof)")
    args = ap.parse_args()
    compile_cache.enable()
    run(
        args.arch, reduced=args.reduced, layers=args.layers, steps=args.steps,
        algorithm=args.algorithm,
        k=args.k, eta=args.eta, tol=args.tol, patience=args.patience,
        m=args.clients, per_client_batch=args.batch,
        seq_len=args.seq, seed=args.seed, ckpt_dir=args.ckpt_dir, resume=args.resume,
        uplink_bits=args.uplink_bits, participation=args.participation,
        popstore_mode={"auto": "auto", "on": True, "off": False}[args.popstore],
        rounds_per_call=args.rounds_per_call, log_every=args.log_every,
        faults=args.faults,
        screen={"auto": "auto", "on": True, "off": False}[args.screen],
        deadline=args.deadline, max_staleness=args.max_staleness,
        stale_gamma=args.stale_gamma,
        async_rounds={"auto": "auto", "on": True, "off": False}[args.async_rounds],
        watchdog=args.watchdog, watchdog_factor=args.watchdog_factor,
        watchdog_patience=args.watchdog_patience, eta_backoff=args.eta_backoff,
        max_rollbacks=args.max_rollbacks, ckpt_every=args.ckpt_every,
        ckpt_keep=args.ckpt_keep, expect_demotions=args.expect_demotions,
        expect_rollbacks=args.expect_rollbacks,
        telemetry=args.telemetry, trace_out=args.trace_out,
        metrics_out=args.metrics_out, profile_rounds=args.profile_rounds,
        profile_dir=args.profile_dir,
    )


if __name__ == "__main__":
    main()
