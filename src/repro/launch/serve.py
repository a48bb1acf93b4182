"""Batched serving launcher: prefill a batch of prompts, then decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 4 --prompt-len 64 --new-tokens 16

Train-while-serve (ISSUE 7): ``--ckpt-dir <dir> --watch`` turns the launcher
into a hot-swap server.  A ``HotSwapWatcher`` polls the trainer's keep-N
checkpoint anchors between query batches, loads new steps with
retry/exponential-backoff (``load_with_retry``), REJECTS truncated or
corrupt files loudly (the step is remembered as bad and never retried), and
keeps serving the last-good parameters when the newest anchor is unreadable
-- the server degrades, it never crashes or serves garbage.  The model is
built and the prefill/decode functions jitted ONCE; a swap only repoints the
parameter pytree, so steady-state query latency is unchanged.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --ckpt-dir /tmp/fedckpt --watch --duration 20

Telemetry (ISSUE 9): ``--trace-out trace.json`` records poll / swap /
prefill / decode spans per query batch (Perfetto-loadable);
``--metrics-out metrics.jsonl`` streams per-query rows and the end-of-run
summary; ``--prom-out serve.prom`` writes the final counters in the
Prometheus textfile-collector format.  All timing below uses the monotonic
``time.perf_counter`` -- wall-clock ``time.time`` can step under NTP and
produce negative latencies; the only wall-clock stamp kept is the history
rows' ``"t"`` field, which is a timestamp, not a duration.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro import telemetry as tel
from repro.configs import get_arch
from repro.launch import compile_cache
from repro.models import build as build_model
from repro.models.model import compute_params


def load_with_retry(ckpt_dir: str, step: int, *, retries: int = 3,
                    backoff: float = 0.05, factor: float = 2.0):
    """``checkpoint.load`` with exponential backoff.  Saves are atomic
    (tmp+fsync+rename), so a transient failure here is a filesystem race --
    e.g. the trainer's keep-N pruning unlinking the step between listing and
    reading -- not a half-written file; a PERSISTENT failure is a genuinely
    truncated/corrupt file and propagates to the caller after ``retries``
    attempts."""
    delay = backoff
    for attempt in range(retries):
        try:
            return ckpt.load(ckpt_dir, step)
        except (FileNotFoundError, ValueError, OSError):
            if attempt == retries - 1:
                raise
            time.sleep(delay)
            delay *= factor
    raise AssertionError("unreachable")


class HotSwapWatcher:
    """Tracks the newest LOADABLE checkpoint under ``ckpt_dir``.

    ``poll()`` walks the on-disk steps newest-first (``checkpoint.steps``,
    not ``latest_step``: a bad file at the newest step must not pin the
    watcher forever), skips steps already rejected, and returns the payload
    of the first new step that loads -- or ``None`` when there is nothing
    newer than the step currently served.  A step whose load still fails
    after the retry/backoff schedule is rejected LOUDLY and remembered in
    ``self.bad``; the caller keeps serving the last-good parameters."""

    def __init__(self, ckpt_dir: str, *, retries: int = 3,
                 backoff: float = 0.05, factor: float = 2.0):
        self.ckpt_dir = ckpt_dir
        self.retries, self.backoff, self.factor = retries, backoff, factor
        self.step: int | None = None  # currently served step
        self.payload = None
        self.bad: set[int] = set()
        self.swaps = 0
        self.failures = 0

    def poll(self):
        cur = -1 if self.step is None else self.step
        for step in sorted(ckpt.steps(self.ckpt_dir), reverse=True):
            if step <= cur:
                break  # nothing newer than what we serve
            if step in self.bad:
                continue  # already rejected; try the next-newest
            try:
                payload = load_with_retry(
                    self.ckpt_dir, step, retries=self.retries,
                    backoff=self.backoff, factor=self.factor)
            except (FileNotFoundError, ValueError, OSError) as e:
                self.bad.add(step)
                self.failures += 1
                print(f"[serve] REJECTED checkpoint step {step}: {e}",
                      flush=True)
                continue
            self.step = step
            self.payload = payload
            self.swaps += 1
            return payload
        return None


def _tel_setup(telemetry: bool, trace_out, metrics_out):
    """Shared launcher telemetry setup: returns (tel_on, tracer, registry,
    sink, was_tracing).  The tracer is the process-global one so library
    code (model, checkpoint) emits into the same trace."""
    tel_on = telemetry or bool(trace_out) or bool(metrics_out)
    tracer = tel.get_tracer()
    was_tracing = tracer.enabled
    if trace_out:
        tracer.configure(enabled=True, trace_out=trace_out)
    registry = tel.Registry() if tel_on else None
    sink = tel.JsonlSink(metrics_out) if metrics_out else None
    return tel_on, tracer, registry, sink, was_tracing


def _tel_teardown(tracer, sink, trace_out, was_tracing):
    if sink is not None:
        sink.close()
    if trace_out:
        path = tracer.close()
        if path:
            print(f"[telemetry] trace written to {path} "
                  f"(load in https://ui.perfetto.dev)", flush=True)
        tracer.configure(enabled=was_tracing)


def run(arch: str, *, reduced: bool = True, batch: int = 4, prompt_len: int = 64,
        new_tokens: int = 16, seed: int = 0, greedy: bool = True,
        telemetry: bool = False, trace_out: str | None = None,
        metrics_out: str | None = None, prom_out: str | None = None):
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.key(seed)
    # weights stored in a state dtype apart from the compute dtype are cast
    # once here, not at every prefill and decode step
    params = compute_params(cfg, model.init(key))

    if cfg.n_codebooks > 1:
        prompts = jax.random.randint(key, (batch, cfg.n_codebooks, prompt_len), 0, cfg.vocab_size)
    else:
        prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    b = {"tokens": prompts}
    if cfg.frontend == "vision":
        b["patches"] = jax.random.normal(jax.random.fold_in(key, 1),
                                         (batch, cfg.n_prefix_tokens, cfg.frontend_dim))

    prefill = jax.jit(lambda p, bb: model.prefill(p, bb, prompt_len + new_tokens + cfg.n_prefix_tokens))
    decode = jax.jit(model.decode)

    tel_on, tracer, registry, sink, was_tracing = _tel_setup(
        telemetry, trace_out, metrics_out)

    t0 = time.perf_counter()
    with tracer.span("serve/prefill", {"batch": batch, "prompt": prompt_len}):
        logits, cache = prefill(params, b)
        logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    def pick(lg):
        if cfg.n_codebooks > 1:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B, K)
            return nxt[:, :, None]
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]

    out_tokens = []
    t0 = time.perf_counter()
    with tracer.span("serve/decode", {"new_tokens": new_tokens}):
        for _ in range(new_tokens):
            nxt = pick(logits)
            logits, cache = decode(params, cache, nxt)
            out_tokens.append(nxt)
        jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(out_tokens, axis=-1)
    n_tok = int(gen.size)
    print(f"[serve] arch={arch} batch={batch} prompt={prompt_len} new={new_tokens}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; decode {t_decode/new_tokens*1e3:.2f} ms/token")
    print(f"[serve] sample generated ids: {jax.device_get(gen)[0][..., :8]}")
    if tel_on:
        registry.counter("serve/tokens").inc(n_tok)
        registry.histogram("serve/prefill_s").observe(t_prefill)
        registry.histogram("serve/decode_s").observe(t_decode)
        registry.gauge("serve/tokens_per_s").set(
            n_tok / t_decode if t_decode > 0 else 0.0)
        if sink is not None:
            sink.write({"kind": "summary", **registry.summary_row()})
        if prom_out:
            print(f"[telemetry] prometheus textfile -> "
                  f"{tel.write_prometheus(registry, prom_out)}", flush=True)
    _tel_teardown(tracer, sink, trace_out, was_tracing)
    return gen


def run_watch(arch: str, *, ckpt_dir: str, reduced: bool = True,
              batch: int = 2, prompt_len: int = 16, new_tokens: int = 4,
              seed: int = 0, poll_interval: float = 0.25,
              duration: float = 30.0, wait_first: float = 60.0,
              stop_when=None, retries: int = 3, backoff: float = 0.05,
              history: list | None = None,
              telemetry: bool = False, trace_out: str | None = None,
              metrics_out: str | None = None, prom_out: str | None = None):
    """Serve queries continuously while a trainer writes checkpoints.

    Blocks until the FIRST loadable checkpoint appears (``wait_first``
    seconds, then ``TimeoutError``), then alternates poll -> swap-if-newer ->
    serve one greedy query batch until ``duration`` elapses or ``stop_when``
    (an optional zero-arg callable, e.g. "the trainer exited and we served
    its final step") returns True.  Returns the per-query history rows
    ``{"t", "step", "round", "tokens"}`` plus the watcher (swap/failure
    counters) for callers that assert on the trajectory; pass ``history``
    (a caller-owned list, appended in place) to watch progress from another
    thread while the loop runs."""
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.key(seed)

    tel_on, tracer, registry, sink, was_tracing = _tel_setup(
        telemetry, trace_out, metrics_out)
    # swap/rejection counters are kept even with telemetry off -- the
    # end-of-run structured summary always prints them
    registry = registry or tel.Registry()

    watcher = HotSwapWatcher(ckpt_dir, retries=retries, backoff=backoff)
    t_first = time.perf_counter()
    payload = watcher.poll()
    while payload is None:
        if time.perf_counter() - t_first > wait_first:
            raise TimeoutError(
                f"no loadable checkpoint appeared under {ckpt_dir} within "
                f"{wait_first:.0f}s")
        time.sleep(poll_interval)
        payload = watcher.poll()
    params = compute_params(cfg, payload["server"])
    print(f"[serve] serving step {watcher.step} "
          f"(round {int(payload['round'])}) from {ckpt_dir}", flush=True)

    if cfg.n_codebooks > 1:
        prompts = jax.random.randint(
            key, (batch, cfg.n_codebooks, prompt_len), 0, cfg.vocab_size)
    else:
        prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    b = {"tokens": prompts}
    if cfg.frontend == "vision":
        b["patches"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (batch, cfg.n_prefix_tokens, cfg.frontend_dim))

    # jit ONCE; hot swaps only repoint the parameter pytree
    prefill = jax.jit(lambda p, bb: model.prefill(
        p, bb, prompt_len + new_tokens + cfg.n_prefix_tokens))
    decode = jax.jit(model.decode)

    def pick(lg):
        if cfg.n_codebooks > 1:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, :, None]
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]

    def query(p):
        with tracer.span("serve/prefill", {"step": watcher.step}):
            logits, cache = prefill(p, b)
            if tracer.enabled:  # sync only when traced: keeps the span honest
                jax.block_until_ready(logits)
        n = 0
        with tracer.span("serve/decode", {"new_tokens": new_tokens}):
            for _ in range(new_tokens):
                nxt = pick(logits)
                logits, cache = decode(p, cache, nxt)
                n += int(nxt.size)
            jax.block_until_ready(logits)
        return n

    history = [] if history is None else history
    t_end = time.perf_counter() + duration
    while True:
        t_poll = time.perf_counter()
        with tracer.span("serve/poll"):
            fresh = watcher.poll()
        if fresh is not None:
            swap_s = time.perf_counter() - t_poll
            payload, params = fresh, compute_params(cfg, fresh["server"])
            registry.histogram("serve/swap_latency_s").observe(swap_s)
            tracer.instant("serve/swap", {"step": watcher.step,
                                          "round": int(payload["round"]),
                                          "latency_s": swap_s})
            print(f"[serve] hot-swapped to step {watcher.step} "
                  f"(round {int(payload['round'])})", flush=True)
        t_q = time.perf_counter()
        n_tok = query(params)
        q_s = time.perf_counter() - t_q
        registry.counter("serve/tokens").inc(n_tok)
        registry.histogram("serve/query_s").observe(q_s)
        row = {"t": time.time(), "step": watcher.step,
               "round": int(payload["round"]), "tokens": n_tok}
        history.append(row)
        if sink is not None:
            sink.write({"kind": "query", "query_s": q_s, **row})
        tracer.flush()
        if stop_when is not None and stop_when():
            break
        if time.perf_counter() >= t_end:
            break
        time.sleep(poll_interval)
    served = sorted({row["step"] for row in history})
    registry.counter("serve/swaps").inc(watcher.swaps)
    registry.counter("serve/rejections").inc(watcher.failures)
    q_hist = registry.histogram("serve/query_s")
    swap_hist = registry.histogram("serve/swap_latency_s")
    tok_total = registry.counter("serve/tokens").value
    tokens_per_s = tok_total / q_hist.total if q_hist.total > 0 else 0.0
    registry.gauge("serve/tokens_per_s").set(tokens_per_s)
    print(f"[serve] {len(history)} query batches; served steps {served}; "
          f"swaps={watcher.swaps} rejected={watcher.failures}", flush=True)
    mean_swap = ("n/a" if swap_hist.count == 0
                 else f"{swap_hist.mean * 1e3:.1f} ms")
    print(f"[serve] summary: tokens={int(tok_total)} "
          f"tokens_per_s={tokens_per_s:.1f} "
          f"mean_query={q_hist.mean * 1e3:.1f} ms "
          f"mean_swap_latency={mean_swap}", flush=True)
    if sink is not None:
        sink.write({"kind": "summary", **registry.summary_row()})
    if prom_out:
        print(f"[telemetry] prometheus textfile -> "
              f"{tel.write_prometheus(registry, prom_out)}", flush=True)
    _tel_teardown(tracer, sink, trace_out, was_tracing)
    return history, watcher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    # --reduced defaults on; --full is the ONLY way to reach full-size
    # serving (a store_true flag that already defaults True is a no-op)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None,
                    help="with --watch: hot-swap serve the trainer's anchors")
    ap.add_argument("--watch", action="store_true",
                    help="train-while-serve: poll --ckpt-dir for new "
                         "checkpoints between query batches")
    ap.add_argument("--poll-interval", type=float, default=0.25)
    ap.add_argument("--duration", type=float, default=30.0,
                    help="watch mode: serve for this many seconds")
    ap.add_argument("--wait-first", type=float, default=60.0,
                    help="watch mode: seconds to wait for the first anchor")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the metrics registry even without sinks")
    ap.add_argument("--trace-out", default=None,
                    help="write poll/swap/prefill/decode spans as Chrome "
                         "trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--metrics-out", default=None,
                    help="stream per-query rows + summary as JSONL")
    ap.add_argument("--prom-out", default=None,
                    help="write final counters as a Prometheus textfile")
    args = ap.parse_args()
    compile_cache.enable()
    tel_kw = dict(telemetry=args.telemetry, trace_out=args.trace_out,
                  metrics_out=args.metrics_out, prom_out=args.prom_out)
    if args.watch:
        if not args.ckpt_dir:
            raise SystemExit("--watch needs --ckpt-dir")
        run_watch(args.arch, ckpt_dir=args.ckpt_dir, reduced=args.reduced,
                  batch=args.batch, prompt_len=args.prompt_len,
                  new_tokens=args.new_tokens,
                  poll_interval=args.poll_interval, duration=args.duration,
                  wait_first=args.wait_first, **tel_kw)
    else:
        run(args.arch, reduced=args.reduced, batch=args.batch,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens, **tel_kw)


if __name__ == "__main__":
    main()
