import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""SSPerf hillclimbing driver: A/B a config override against the baseline on
the full production config (16x16 mesh), reporting the roofline-relevant
deltas (per-device memory, HLO flops/bytes, collective bytes/count).

    PYTHONPATH=src python -m repro.launch.perf --arch deepseek-v2-lite-16b \
        --shape train_4k --set moe_fused_dispatch=True --tag H1

Writes experiments/perf/<arch>_<shape>_<tag>.json with {baseline, variant,
delta}.  The EXPERIMENTS.md SSPerf log references these artifacts.
"""
import argparse
import dataclasses
import json
import pathlib
import time

import jax

from repro.configs import get_arch, get_shape
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

REPORT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "perf"


def _parse_val(v: str):
    if v in ("True", "False"):
        return v == "True"
    if v == "None":
        return None
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def measure(cfg, shape, *, multi_pod=False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = build_step(cfg, shape, mesh)
    # monotonic perf_counter, not time.time: compile-time deltas between
    # baseline and variant are part of the A/B report
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            bundle.fn,
            in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        ).lower(*bundle.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    flops, bytes_acc = hlo_stats.flops_and_bytes(compiled)
    mem = hlo_stats.memory_stats(compiled)
    coll = hlo_stats.collective_bytes(compiled.as_text())
    return {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "memory_peak_gib": mem["peak_bytes_est"] / 2**30,
        "memory_args_gib": mem["argument_bytes"] / 2**30,
        "collective_bytes_mib": coll["total"]["bytes"] / 2**20,
        "collective_count": coll["total"]["count"],
        "collective_detail": {
            k: {"count": v["count"], "mib": round(v["bytes"] / 2**20, 1)}
            for k, v in coll.items() if k != "total"
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (repeatable); fed.* allowed")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-baseline", action="store_true",
                    help="reuse baseline from an existing report with this tag")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    shape = get_shape(args.shape)

    var = cfg
    fed_over = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if k.startswith("fed."):
            fed_over[k[4:]] = _parse_val(v)
        else:
            var = dataclasses.replace(var, **{k: _parse_val(v)})
    if fed_over:
        var = dataclasses.replace(var, fed=dataclasses.replace(var.fed, **fed_over))

    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    out_path = REPORT_DIR / f"{args.arch}_{args.shape}_{args.tag}.json"

    if args.skip_baseline and out_path.exists():
        base = json.loads(out_path.read_text())["baseline"]
    else:
        print(f"[perf] baseline {args.arch} {args.shape} ...")
        base = measure(cfg, shape, multi_pod=args.multi_pod)
    print(f"[perf] variant  {args.tag}: {args.set} ...")
    variant = measure(var, shape, multi_pod=args.multi_pod)

    def pct(b, v):
        return None if not b else round(100.0 * (v - b) / b, 2)

    delta = {
        k: pct(base[k], variant[k])
        for k in ("hlo_flops_per_device", "hlo_bytes_per_device",
                  "memory_peak_gib", "memory_args_gib",
                  "collective_bytes_mib", "collective_count")
    }
    report = {
        "arch": args.arch, "shape": args.shape, "tag": args.tag,
        "overrides": args.set, "mesh": "2x16x16" if args.multi_pod else "16x16",
        "baseline": base, "variant": variant, "delta_pct": delta,
    }
    out_path.write_text(json.dumps(report, indent=2))
    print(json.dumps({"delta_pct": delta,
                      "baseline_coll": base["collective_detail"],
                      "variant_coll": variant["collective_detail"]}, indent=2))


if __name__ == "__main__":
    main()
