"""ISSUE 9: the telemetry subsystem -- span tracing, the metrics registry,
and the sinks.

Contracts pinned here:

  * span nesting and ordering survive into valid Chrome trace-event JSON
    (plain ``json.load``-able once closed; Perfetto wants exactly this);
  * a DISABLED tracer is a true no-op: per-span allocations do not scale
    with call count (the ``_NULL_SPAN`` singleton / fixed-arity
    ``__exit__`` design);
  * the registry's counter totals from a short FAULTED train run equal the
    hand-computed sum over the per-round rows the same run streamed to the
    JSONL sink -- i.e. the registry matches the launcher's own
    ``--expect-demotions`` accounting rather than double- or
    under-counting;
  * the JSONL sink tolerates a crash-torn final line but refuses mid-file
    corruption; ``load_trace`` recovers every event flushed before a
    crash that never wrote the closing ``]``;
  * ``write_prometheus`` emits the textfile-collector format (sanitised
    names, ``_total`` counters, histogram moments) atomically.
"""
import json
import sys
import threading

import pytest

from repro import telemetry as tel
from repro.launch.train import run as train_run
from repro.telemetry.spans import _NULL_SPAN, Tracer, load_trace


# -- spans -------------------------------------------------------------------


def test_span_nesting_and_chrome_trace_json(tmp_path):
    path = tmp_path / "trace.json"
    tr = Tracer().configure(enabled=True, trace_out=path)
    with tr.span("outer", {"round": 1}):
        with tr.span("inner"):
            pass
        tr.instant("mark", {"k": 3})
    tr.counter("ring", {"hit": 2, "miss": 1})
    tr.flush()
    assert tr.close() == str(path)

    # a CLOSED trace is a plain JSON array -- exactly what Perfetto loads
    events = json.loads(path.read_text())
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner", "mark", "ring"}

    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"] == {"round": 1}
    # spans record on exit, so the INNER event precedes the outer in the
    # stream; nesting is recovered from the timestamps (ts microseconds)
    assert events.index(inner) < events.index(outer)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert all(e["pid"] == outer["pid"] for e in events)

    mark = by_name["mark"]
    assert (mark["ph"], mark["s"]) == ("i", "t")
    assert mark["args"] == {"k": 3}
    ring = by_name["ring"]
    assert ring["ph"] == "C" and ring["args"] == {"hit": 2, "miss": 1}


def test_scalar_counter():
    tr = Tracer().configure(enabled=True)
    tr.counter("hits", 7)
    tr.counter("ring", {"hit": 1})
    events = tr.drain()
    assert {"ph": "C", "args": {"value": 7}}.items() <= events[0].items()
    assert events[1]["args"] == {"hit": 1}
    tr.configure(enabled=False)
    tr.counter("hits", 8)  # a disabled tracer records nothing
    assert tr.drain() == []


def test_disabled_tracer_is_allocation_free():
    tr = Tracer()  # enabled=False
    assert tr.span("x") is _NULL_SPAN
    assert tr.span("y", {"a": 1}) is _NULL_SPAN

    def burn(n):
        for _ in range(n):
            with tr.span("hot/phase", None):
                pass
            tr.instant("i")
            tr.counter("c", 1)

    def blocks(n):
        burn(64)  # warm up any lazy interpreter state
        before = sys.getallocatedblocks()
        burn(n)
        return sys.getallocatedblocks() - before

    # ambient interpreter noise is a few blocks and CONSTANT; a single
    # allocation per disabled call would show up as >= n
    small, large = blocks(100), blocks(20_000)
    assert large - small < 64, (small, large)


def test_tracer_threads_get_own_tid():
    tr = Tracer().configure(enabled=True)

    def work():
        with tr.span("t/span"):
            pass

    th = threading.Thread(target=work)
    th.start()
    th.join()
    with tr.span("main/span"):
        pass
    tids = {e["tid"] for e in tr.drain()}
    assert len(tids) == 2


def test_load_trace_recovers_crash_truncated_file(tmp_path):
    path = tmp_path / "trace.json"
    tr = Tracer().configure(enabled=True, trace_out=path)
    for i in range(3):
        with tr.span(f"s{i}"):
            pass
    tr.flush()  # no close(): simulates a killed run (no closing "]")
    text = path.read_text()
    assert not text.rstrip().endswith("]")
    with pytest.raises(json.JSONDecodeError):
        json.loads(text)
    events = load_trace(path)
    assert [e["name"] for e in events] == ["s0", "s1", "s2"]

    # torn final line on top of the missing terminator
    path.write_text(text[: len(text) - 7])
    assert [e["name"] for e in load_trace(path)] == ["s0", "s1"]
    tr.close()


# -- registry ----------------------------------------------------------------


def test_registry_kinds_and_absorb():
    reg = tel.Registry()
    reg.counter("n").inc(2)
    reg.counter("n").inc(3)
    assert reg.counter("n").value == 5
    with pytest.raises(ValueError):
        reg.counter("n").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("n")  # kind collision is loud

    reg.absorb({"server_loss": 2.0, "faults_injected": 3, "note": "text"})
    reg.absorb({"server_loss": 4.0, "faults_injected": 1})
    snap = reg.snapshot()
    assert snap["faults_injected"] == 4.0  # COUNTER_KEYS sum
    assert snap["server_loss"] == 4.0  # gauge keeps the last value
    h = snap["server_loss_hist"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 6.0, 2.0, 4.0)
    assert "note" not in snap

    # counters=() defers counter-semantic keys to a caller with a more
    # complete stream: they must be SKIPPED, not re-registered as gauges
    reg.absorb({"faults_injected": 99.0, "server_loss": 1.0}, counters=())
    assert reg.snapshot()["faults_injected"] == 4.0


def test_registry_totals_match_faulted_train_accounting(tmp_path):
    """End-to-end: a short faulted train run streams per-round rows to the
    JSONL sink; the summary row's fault counters must equal the hand-summed
    per-round counts (log_every=1 and R=1 make the logged rows a complete
    cover of the dispatches, so the sum IS the launcher's accounting)."""
    metrics_path = tmp_path / "metrics.jsonl"
    train_run("olmo-1b", reduced=True, steps=4, m=8, per_client_batch=2,
              seq_len=32, k=1, eta=0.05, participation=0.5,
              popstore_mode=True, faults="corrupt=0.3,seed=7",
              log_every=1, metrics_out=str(metrics_path))
    rows = tel.read_jsonl(metrics_path)
    rounds = [r for r in rows if r["kind"] == "round"]
    (summary,) = [r for r in rows if r["kind"] == "summary"]
    assert len(rounds) == 4
    assert summary["faults_injected"] == sum(
        r["faults_injected"] for r in rounds) > 0
    assert summary["faults_demoted"] == sum(
        r["faults_demoted"] for r in rounds)
    # histogram of the logged loss covers every logged row
    assert summary["server_loss_hist_count"] == 4
    assert summary["server_loss"] == rounds[-1]["server_loss"]
    # the global tracer must be left OFF for the rest of the session
    assert not tel.enabled()


def test_train_telemetry_off_leaves_global_tracer_alone(tmp_path):
    train_run("olmo-1b", reduced=True, steps=2, m=4, per_client_batch=2,
              seq_len=32, k=1, eta=0.05, log_every=1)
    assert not tel.enabled()


# -- sinks -------------------------------------------------------------------


def test_jsonl_sink_torn_tail_tolerated_midfile_corruption_raises(tmp_path):
    path = tmp_path / "m.jsonl"
    with tel.JsonlSink(path) as sink:
        sink.write({"a": 1})
        sink.write({"a": 2})
    with open(path, "a") as f:
        f.write('{"a": 3, "tor')  # crash mid-row
    assert [r["a"] for r in tel.read_jsonl(path)] == [1, 2]

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"a": 1}\n{torn}\n{"a": 3}\n')
    with pytest.raises(json.JSONDecodeError):
        tel.read_jsonl(bad)  # mid-file corruption is NOT truncation


def test_prometheus_textfile_format(tmp_path):
    reg = tel.Registry()
    reg.counter("serve/tokens").inc(128)
    reg.gauge("eta_scale").set(0.5)
    h = reg.histogram("swap_latency_s")
    h.observe(0.1)
    h.observe(0.3)
    out = tmp_path / "metrics.prom"
    assert tel.write_prometheus(reg, out) == str(out)
    text = out.read_text()
    lines = text.splitlines()
    assert "# TYPE repro_serve_tokens_total counter" in lines
    assert "repro_serve_tokens_total 128.0" in lines  # name sanitised: / -> _
    assert "repro_eta_scale 0.5" in lines
    assert "repro_swap_latency_s_count 2.0" in lines
    assert any(ln.startswith("repro_swap_latency_s_mean 0.2") for ln in lines)
    assert text.endswith("\n")
    # every sample line parses as "name value" with a legal metric name
    for ln in lines:
        if ln.startswith("#"):
            continue
        name, val = ln.split(" ")
        assert tel.metrics._NAME_OK.match(name), name
        float(val)
    assert not out.with_suffix(out.suffix + ".tmp").exists()  # atomic write


# -- the program's spans and scopes on the profiler's clock -------------------


def _host_events(trace_dir, name):
    """Events called ``name`` on ``/host:CPU`` of the profiler capture."""
    import glob
    import gzip

    (path,) = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    return [e for e in events if e.get("name") == name
            and procs.get(e.get("pid")) == "/host:CPU"]


def test_enabled_span_lands_on_the_profiler_host_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    on, off = Tracer().configure(enabled=True), Tracer()
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    try:
        with on.span("round/dispatch"):
            jnp.arange(8.0).sum().block_until_ready()
        with off.span("round/never"):
            pass
    finally:
        jax.profiler.stop_trace()
    (ev,) = _host_events(tmp_path, "round/dispatch")
    assert ev["ph"] == "X" and ev["dur"] > 0
    assert _host_events(tmp_path, "round/never") == []
    # the tracer's own record of the span is kept as before
    assert [e["name"] for e in on.drain()] == ["round/dispatch"]


def test_profile_rounds_puts_round_spans_on_the_trace_without_trace_out(tmp_path):
    prof_dir = tmp_path / "prof"
    train_run("olmo-1b", reduced=True, steps=3, m=2, per_client_batch=1,
              seq_len=16, k=1, eta=0.05, log_every=1,
              profile_rounds="2:2", profile_dir=str(prof_dir))
    assert len(_host_events(prof_dir, "round/dispatch")) == 1
    assert len(_host_events(prof_dir, "round/block_until_ready")) == 1
    # the tracer was on for the window alone, and kept none of its events
    assert not tel.enabled()
    assert tel.get_tracer().drain() == []


def test_compile_counter_counts_each_compile(tmp_path):
    import jax
    import jax.numpy as jnp

    tel.compiles.install()
    tel.compiles.install()  # one listener, however often installed
    x7, x9 = jnp.ones(7), jnp.ones(9)
    n0, s0 = tel.compiles.totals()
    tracer = tel.get_tracer()
    tracer.drain()
    tracer.configure(enabled=True)
    try:
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        f(x7).block_until_ready()
        f(x7).block_until_ready()  # same shape: no new compile
        n1, s1 = tel.compiles.totals()
        f(x9).block_until_ready()  # new shape: one more
        n2, _ = tel.compiles.totals()
    finally:
        tracer.configure(enabled=False)
    assert n1 - n0 == 1 and s1 > s0
    assert n2 - n1 == 1
    marks = [e for e in tracer.drain() if e["name"] == "jit/compile"]
    assert len(marks) == 2 and all(e["ph"] == "i" for e in marks)

    # train.run's summary reports what its own run compiled
    path = tmp_path / "m.jsonl"
    train_run("olmo-1b", reduced=True, steps=2, m=2, per_client_batch=1,
              seq_len=16, k=1, eta=0.05, log_every=1, metrics_out=str(path))
    (summary,) = [r for r in tel.read_jsonl(path) if r["kind"] == "summary"]
    assert summary["jit/compiles"] >= 1 and summary["jit/compile_s"] > 0


PHASES = ("round.inner_loop", "round.client_grad", "arena_pack",
          "round.client_update", "fused_update_client", "round.uplink",
          "round_tail", "round.server_mean", "round.dual_refresh",
          "dual_from_uplink", "round.metrics")


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_round_phases_name_the_compiled_ops(impl, monkeypatch):
    """Every phase of an arena round names its ops in the compiled HLO, and
    the Pallas kernels read the arena as it lies: no tiling pad around
    them, nothing under ``relayout``."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs.base import FederatedConfig
    from repro.core import make
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_impl", lambda: impl)
    params = {"w": jnp.full((300,), 0.1), "b": jnp.zeros((7,))}
    fed = make(FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.05,
                               num_clients=3, use_arena=True))

    def grad_fn(p, b):  # a plain gradient: the pytree/arena round trip
        return jax.grad(lambda q: jnp.sum((q["w"][:7] * b + q["b"]) ** 2))(p)

    state = fed.init(params, 3)
    hlo = jax.jit(lambda s, b: fed.round(s, grad_fn, b)).lower(
        state, jnp.ones((3, 7))).compile().as_text()
    ops_named = re.findall(r"= \S+ (\w[\w-]*)\(.*op_name=\"([^\"]*)\"", hlo)
    stacks = [n for _, n in ops_named]
    for scope in PHASES:
        assert any(f"/{scope}/" in n for n in stacks), scope
    tiling = [(op, n) for op, n in ops_named if op == "pad"
              and re.search(r"/(fused_update_client|round_tail|dual_from_uplink)/", n)]
    assert not tiling, tiling
    assert not any("/relayout/" in n for n in stacks)
