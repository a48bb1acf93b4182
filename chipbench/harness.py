"""Run one benchmark cell once: load, warm up, check, measure, print.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
workload names its configuration (``configs[].file``) and its traffic
(``chipbench/traffic/<traffic>.json``); the configuration names its kind of
objective (``chipbench/objectives/<objective>.py``); the limits that decide
``correct`` are in ``chipbench/limits/<workload>.json``; each per-layer
metric is read by ``chipbench/metrics/<metric>.py``.  A new cell, traffic
mix, configuration or metric is added as files.

A run: set-up (device check, weights and data from the seed, the program's
round compiled ahead of time, then the checked rounds, which go through the
window's own call), the measured window of ``--seconds``, the peak device
memory, then the plain reference over the checked rounds and the
comparison.  ``setup_s`` runs from process start to the end of the checked
rounds.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
PKG = HERE.name  # the benchmark's directory under the repository root


class NoChip(RuntimeError):
    """The devices JAX found cannot run the cell."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(f"_{PKG}_" + path.stem.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(workload: str, root=REPO) -> dict:
    """Everything the named workload needs, from ``root``'s files."""
    root = pathlib.Path(root)
    bench = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}")
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(root / conf["file"])
    return {
        "workload": wl,
        "config": config,
        "traffic": load_json(root / PKG / "traffic" / f"{wl['traffic']}.json"),
        "limits": load_json(root / PKG / "limits" / f"{workload}.json")["limits"],
        "objective": root / PKG / "objectives" / f"{config['objective']}.py",
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, workload)],
        "per_layer": [dict(m, reader=root / PKG / "metrics" / f"{m['name']}.py")
                      for m in bench["per_layer"] if _applies(m, workload)],
    }


def check_devices(devices, chips: int, peaks: dict) -> dict:
    """The peak table entry of the cell's devices.  Raises ``NoChip`` unless
    JAX found at least ``chips`` TPUs, and ``KeyError`` for a TPU kind
    missing from the table."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise NoChip(f"needs a TPU, JAX found {found}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return peaks["devices"][kind]


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"{PKG}/{name}")


def _finite(metrics) -> bool:
    import numpy as np

    return all(math.isfinite(float(np.asarray(v).reshape(-1)[0]))
               for v in metrics.values())


def _drive(obj, seconds: float):
    """The measured window: rounds until the first that ends after
    ``seconds``; each round's metrics reach the host before the next round
    is dispatched."""
    import jax

    rounds = failed = 0
    dispatch = []
    t0 = time.perf_counter()
    while True:
        with _span("round"):
            a = time.perf_counter()
            with _span("dispatch"):
                met = obj.step()
            dispatch.append(time.perf_counter() - a)
            with _span("fetch"):
                met = jax.device_get(met)
        rounds += 1
        failed += not _finite(met)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return {"rounds": rounds, "failed": failed, "window_s": elapsed,
                    "dispatch_s": dispatch}


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        root=REPO, check: bool = True, out_dir=None):
    """One run of one cell; returns the result line as a dict."""
    import jax

    from chipbench import compare
    from chipbench import trace as tracemod

    c = cell(workload, root)
    chips = c["workload"]["chips"]
    devices = jax.devices()
    peaks = load_json(HERE / "peaks.json")
    # check=False drives the run on whatever devices there are (tests on the
    # CPU), with the first table entry standing in for the peaks
    peak = (check_devices(devices, chips, peaks) if check
            else next(iter(peaks["devices"].values())))
    devices = devices[:chips]
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    obj = load_module(c["objective"]).Objective(c["config"], c["traffic"], devices, seed)
    obj.setup()
    prog = {"rounds": [], "drift": []}
    failed = 0
    for _ in range(obj.checked):
        with _span("round"):
            met = jax.device_get(obj.step())
        failed += not _finite(met)
        prog["drift"].append(float(met["client_drift"]))
        prog["rounds"].append(obj.readings())
    setup_s = time.perf_counter() - t_start

    out_dir = pathlib.Path(out_dir or pathlib.Path(root) / f"{PKG}_out" /
                           f"{workload}.{seed}.trace{int(trace)}")
    tdir = out_dir / "trace"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir), create_perfetto_trace=True)
    win = _drive(obj, seconds)
    if trace:
        jax.profiler.stop_trace()
    peak_mem = peak_bytes(devices)
    win["memory_stats"] = devices[0].memory_stats()
    obj.close()

    ref = obj.reference(c["config"]["dtype"])
    nums = compare.numbers(prog, ref)
    checks = {k: {"value": nums[k], "limit": lim} for k, lim in c["limits"].items()}
    failed += win["failed"]
    correct = failed == 0 and all(v["value"] <= v["limit"] for v in checks.values())

    round_s = win["window_s"] / win["rounds"]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    result = {"correct": correct, "attempted": win["rounds"] + obj.checked,
              "failed": failed}
    if not trace:
        values = {"round_s": round_s, "peak_hbm_gb": peak_mem / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    else:
        tr = tracemod.load(tracemod.find(str(tdir)))
        red = tracemod.reduce(tr)
        device |= {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        # per-round readings count the rounds the trace holds, which can be
        # fewer than the window ran when the profiler drops early events
        traced = sum(1 for s in tr["spans"] if s[0] == f"{PKG}/round")
        ctx = {"trace": tr, "reduced": red, "window": tracemod.window_of(tr["spans"]),
               "rounds": traced, "round_s": round_s,
               "dispatch_s": win["dispatch_s"], "chips": chips, "peak": peak,
               "counts": obj.counts()}
        metrics = {}
        for m in c["per_layer"]:
            v = load_module(m["reader"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        out_dir.mkdir(parents=True, exist_ok=True)
        summary = {k: v for k, v in red.items() if k != "op_seconds"}
        summary["lines"] = tr["lines"]
        (out_dir / "trace_summary.json").write_text(json.dumps(summary, indent=1))
        shutil.rmtree(tdir, ignore_errors=True)
    if obj.memory is not None:
        ma = obj.memory
        print(f"[{PKG}] compiled round: arguments {ma.argument_size_in_bytes} B, "
              f"temporaries {ma.temp_size_in_bytes} B, outputs "
              f"{ma.output_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B",
              file=sys.stderr)
    print(f"[{PKG}] memory_stats after the window: "
          f"{json.dumps(win['memory_stats'])}", file=sys.stderr)
    print(f"[{PKG}] numbers: {json.dumps(nums)}; leaves kept "
          f"{compare.kept_leaves(ref['grad0']).tolist()}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    result |= {"metrics": metrics, "device": device, "checks": checks}
    return result


def main(args, t_start: float) -> int:
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start)
    except NoChip as e:
        print(f"[{PKG}] {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
