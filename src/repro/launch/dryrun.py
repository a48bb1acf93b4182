import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input shape) this lowers + compiles the appropriate
step on the production mesh(es) with ShapeDtypeStruct inputs (no allocation),
prints ``memory_analysis()`` / ``cost_analysis()``, parses collective traffic
from the partitioned HLO, and writes one JSON report per combination under
``experiments/dryrun/``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                  # full matrix
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod      # 2x16x16
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_arch, get_shape
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

REPORT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_one(arch_name: str, shape_name: str, *, multi_pod: bool, verbose: bool = True) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    if not cfg.supports_shape(shape):
        return {
            "arch": arch_name, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skipped",
            "reason": "full-attention arch without SW variant; see DESIGN.md",
        }

    mesh = make_production_mesh(multi_pod=multi_pod)
    # monotonic clock: wall-clock time.time can step under NTP and skew
    # the lower/compile durations the reports record
    t0 = time.perf_counter()
    bundle = build_step(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            bundle.fn,
            in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        )
        lowered = jitted.lower(*bundle.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    flops, bytes_acc = hlo_stats.flops_and_bytes(compiled)
    mem = hlo_stats.memory_stats(compiled)
    coll = hlo_stats.collective_bytes(compiled.as_text())

    report = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "step": bundle.name,
        "meta": bundle.meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # NOTE: per-device numbers; lax.scan bodies are counted once by XLA's
        # cost analysis -- launch.roofline does the depth extrapolation.
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "memory": mem,
        "collectives": coll,
    }
    if verbose:
        gb = mem["peak_bytes_est"] / 2**30
        print(
            f"[dryrun] {arch_name:28s} {shape_name:12s} mesh={report['mesh']:8s} "
            f"{bundle.name:13s} mem/device~{gb:6.2f}GiB flops/dev={flops:.3e} "
            f"coll={coll['total']['count']:3d} ops {coll['total']['bytes']/2**20:9.1f}MiB "
            f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)"
        )
        print(f"         memory_analysis: {compiled.memory_analysis()}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true", help="run 16x16 AND 2x16x16")
    ap.add_argument("--out", default=str(REPORT_DIR))
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for multi_pod in meshes:
        for a in archs:
            for s in shapes:
                tag = f"{a}_{s}_{'2x16x16' if multi_pod else '16x16'}"
                try:
                    rep = run_one(a, s, multi_pod=multi_pod)
                    if rep["status"] == "ok":
                        n_ok += 1
                    else:
                        n_skip += 1
                        print(f"[dryrun] {a:28s} {s:12s} SKIP ({rep['reason']})")
                except Exception as e:  # a failure here is a sharding bug
                    n_fail += 1
                    rep = {
                        "arch": a, "shape": s,
                        "mesh": "2x16x16" if multi_pod else "16x16",
                        "status": "failed", "error": f"{type(e).__name__}: {e}",
                    }
                    print(f"[dryrun] {a:28s} {s:12s} FAIL: {e}")
                    traceback.print_exc(limit=3)
                (outdir / f"{tag}.json").write_text(json.dumps(rep, indent=2))
    print(f"\n[dryrun] ok={n_ok} skipped={n_skip} FAILED={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
