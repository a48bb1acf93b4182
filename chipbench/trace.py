"""Reduce a ``jax.profiler`` trace to device busy time, device time per
operation, and idle gaps labelled by the host span that was open at the
time.

The profiler writes the trace twice under ``<dir>/plugins/profile/<time>/``:
``*.xplane.pb`` and, with ``create_perfetto_trace=True``,
``*.trace.json.gz``.  Only the second carries each device operation's
metadata (its HLO text ``long_name``, its JAX name stack ``tf_op``, its
``source`` line), so ``load`` reads that one, with ``gzip`` and ``json``.
On a TPU the operations are the events of thread "XLA Ops" of process
"/device:TPU:<n>"; the benchmark's host spans ("chipbench/...") are events
of process "/host:CPU".  ``reduce`` works on what ``load`` returns, so it
can be checked on events written by hand.  Times are nanoseconds on the
profiler's common clock.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os

SPAN_PREFIX = "chipbench/"
# metadata of a device operation kept for the readers
KEPT_ARGS = ("long_name", "tf_op", "source_stack", "hlo_category")
# ops that contain other ops on the same line (their body's ops are events
# of their own): counted in busy time, never attributed as an operation
CONTAINERS = ("while", "conditional", "call")


def tpu_ops(process: str, thread: str):
    """Device id of a (process, thread) that holds TPU operations, else None."""
    if process.startswith("/device:TPU:") and thread == "XLA Ops":
        return process[len("/device:"):]
    return None


def find(directory: str) -> str:
    """The newest ``.trace.json.gz`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .trace.json.gz under {directory}")
    return max(paths, key=os.path.getmtime)


def load(path: str, select=tpu_ops) -> dict:
    """{"devices": {device: [[name, start_ns, dur_ns, args], ...]},
        "spans": [[name, start_ns, dur_ns], ...],
        "lines": {process: [thread, ...]}}"""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e["name"] == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    devices, spans = collections.defaultdict(list), []
    for e in events:
        if e.get("ph") != "X":
            continue
        start, dur = e["ts"] * 1e3, e.get("dur", 0.0) * 1e3
        dev = select(proc.get(e["pid"], ""), thread.get((e["pid"], e["tid"]), ""))
        if dev is not None:
            args = e.get("args", {})
            devices[dev].append([e["name"], start, dur,
                                 {k: args[k] for k in KEPT_ARGS if k in args}])
        elif e["name"].startswith(SPAN_PREFIX):
            spans.append([e["name"], start, dur])
    lines = collections.defaultdict(list)
    for (pid, _), name in thread.items():
        lines[proc.get(pid, str(pid))].append(name)
    return {"devices": dict(devices), "spans": sorted(spans, key=lambda s: s[1]),
            "lines": {k: sorted(v) for k, v in lines.items()}}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_of(spans, name: str = SPAN_PREFIX + "round"):
    """[first start, last end] of the spans called ``name``."""
    sel = [(s, s + d) for n, s, d in spans if n == name]
    if not sel:
        raise ValueError(f"no {name} span in the trace")
    return min(a for a, _ in sel), max(b for _, b in sel)


def label_at(spans, t: float) -> str:
    """Innermost benchmark span open at time t ("none" if there is none)."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def op_label(name: str, args: dict) -> str:
    """An op's name with its JAX name stack, where the trace has one."""
    stack = args.get("tf_op")
    return f"{name} {stack}" if stack else name


def reduce(tr: dict, window=None, top: int = 10) -> dict:
    """Busy and idle time per device inside ``window`` (default: the round
    spans), device seconds per operation name (mean over devices), and the
    ``top`` longest idle gaps labelled by the host span open in their
    middle."""
    if window is None:
        window = window_of(tr["spans"])
    t0, t1 = window
    busy, per_op, gaps = {}, collections.Counter(), []
    for dev, evs in tr["devices"].items():
        ivs = []
        for name, s, d, args in evs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                ivs.append((a, b))
                if args.get("hlo_category") not in CONTAINERS:
                    per_op[op_label(name, args)] += (b - a) / len(tr["devices"])
        u = _union(ivs)
        busy[dev] = sum(b - a for a, b in u)
        edges = [t0] + [x for iv in u for x in iv] + [t1]
        gaps += [(b - a, (a + b) / 2) for a, b in zip(edges[::2], edges[1::2])
                 if b > a]
    span_s = (t1 - t0) * 1e-9
    busy_s = sum(busy.values()) / max(len(busy), 1) * 1e-9
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": span_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / span_s if span_s > 0 else None,
        "device_ops": [[n, t * 1e-9] for n, t in per_op.most_common(top)],
        "op_seconds": {n: t * 1e-9 for n, t in per_op.items()},
        "idle_gaps": [[label_at(tr["spans"], mid), g * 1e-9]
                      for g, mid in gaps[:top]],
    }


def device_seconds(tr: dict, match, window) -> float:
    """Device seconds inside ``window`` of the ops for which
    ``match(name, stats)`` holds, mean over devices."""
    t0, t1 = window
    total = 0.0
    for evs in tr["devices"].values():
        for name, s, d, stats in evs:
            if match(name, stats):
                total += max(0, min(s + d, t1) - max(s, t0))
    return total / max(len(tr["devices"]), 1) * 1e-9


def count_ops(tr: dict, match, window) -> float:
    """Number of ops for which ``match`` holds that start inside
    ``window``, mean over devices."""
    t0, t1 = window
    n = sum(1 for evs in tr["devices"].values() for name, s, d, stats in evs
            if t0 <= s < t1 and match(name, stats))
    return n / max(len(tr["devices"]), 1)
