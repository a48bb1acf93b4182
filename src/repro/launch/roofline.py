import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline analysis from compiled dry-run artifacts (TPU v5e targets).

Terms per (arch x shape) on the single-pod 16x16 mesh, all in seconds:

    compute    = HLO_FLOPs_per_device / PEAK_FLOPS_BF16
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_bytes_per_device / ICI_BW

XLA's cost analysis counts a ``lax.scan`` body once, so the depth totals come
from TWO probe lowerings with 1 and 2 *unrolled* pattern units:
per_unit = probe2 - probe1, total = probe1 + per_unit * (n_units - 1 +
tail/pattern).  Probes use inner_steps K=1; the compute/memory terms scale by
K (client-local), the round collective does NOT (the paper's communication
claim) -- the report carries both K=1 and K-scaled compute columns.

MODEL_FLOPS = 6 N_active D (train) / 2 N_active D (inference) gives the
useful-work ratio against HLO FLOPs (catching remat/dispatch waste).
"""
import argparse
import dataclasses
import json
import pathlib

import jax

from repro.configs import ARCHS, SHAPES, get_arch, get_shape
from repro.launch import hlo_stats
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.launch.steps import build_step

REPORT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "roofline"
CHIPS = 256


def _probe_cfg(cfg, n_units: int):
    """Unrolled, K=1, no-grad-accum probe with `n_units` pattern units.

    ``microbatch=None``: the grad-accumulation ``lax.scan`` body is counted
    ONCE by XLA's cost analysis (verified: an 8-chunk scanned grad reports
    1/8th the flops of the equivalent plain grad), which silently divided the
    compute term by up to 64x (llama4).  Accumulation does not change the
    round's total FLOPs, so the single-pass probe measures them correctly;
    it *does* change HBM traffic (params re-read per chunk), so the memory
    term is reported for the microbatch=1 schedule -- noted in EXPERIMENTS.md.
    """
    n_layers = cfg.first_dense_layers + cfg.pattern_len * n_units
    return dataclasses.replace(
        cfg,
        n_layers=n_layers,
        scan_layers=False,
        microbatch=None,
        fed=dataclasses.replace(cfg.fed, inner_steps=1),
    )


def _measure(cfg, shape, mesh):
    bundle = build_step(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            bundle.fn, in_shardings=bundle.in_shardings, out_shardings=bundle.out_shardings
        ).lower(*bundle.args)
        compiled = lowered.compile()
    flops, bytes_acc = hlo_stats.flops_and_bytes(compiled)
    coll = hlo_stats.collective_bytes(compiled.as_text())
    return {
        "flops": flops,
        "bytes": bytes_acc,
        "coll_bytes": float(coll["total"]["bytes"]),
        "coll_count": coll["total"]["count"],
        "coll_detail": {k: v for k, v in coll.items() if k != "total"},
    }


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs for the whole step (all chips)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens * cfg.fed.inner_steps
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def analyze(arch_name: str, shape_name: str, *, verbose=True) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    if not cfg.supports_shape(shape):
        return {"arch": arch_name, "shape": shape_name, "status": "skipped"}
    mesh = make_production_mesh(multi_pod=False)

    p1 = _measure(_probe_cfg(cfg, 1), shape, mesh)
    p2 = _measure(_probe_cfg(cfg, 2), shape, mesh)

    lead, = (cfg.first_dense_layers,)
    n_units = (cfg.n_layers - lead) // cfg.pattern_len
    tail_frac = ((cfg.n_layers - lead) % cfg.pattern_len) / cfg.pattern_len
    mult = (n_units - 1) + tail_frac

    def extrap(key):
        per_unit = max(0.0, p2[key] - p1[key])
        return p1[key] + per_unit * mult

    flops_dev = extrap("flops")
    bytes_dev = extrap("bytes")
    coll_dev = extrap("coll_bytes")

    K = cfg.fed.inner_steps if shape.kind == "train" else 1
    t_compute = flops_dev / PEAK_FLOPS_BF16
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    hlo_total = flops_dev * CHIPS * K
    ratio = mf / hlo_total if hlo_total else float("nan")

    recs = {
        "compute": "raise arithmetic efficiency: fuse attention (Pallas flash), "
                   "drop masked-block FLOPs (causal_skip), larger per-step tiles",
        "memory": "cut HBM traffic: fused client update (1 pass), bf16 collective "
                  "dtypes, larger microbatches once capacity allows, remat policy tuning",
        "collective": "overlap/shrink collectives: bf16 all-reduce, combine the "
                      "round's uplink tensors, hierarchical (pod-local first) reduction",
    }

    report = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "16x16",
        "status": "ok",
        "K": K,
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_dev,
        },
        "terms_seconds": terms,
        "terms_seconds_k_scaled": {
            "compute": t_compute * K,
            "memory": t_memory * K,
            "collective": t_coll,  # round-level: amortised over K (the paper's point)
        },
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": ratio,
        "bottleneck_note": recs[dominant],
        "collective_detail": p2["coll_detail"],
    }
    if verbose:
        print(
            f"[roofline] {arch_name:28s} {shape_name:12s} "
            f"compute={t_compute*1e3:9.3f}ms memory={t_memory*1e3:9.3f}ms "
            f"collective={t_coll*1e3:9.3f}ms -> {dominant:10s} "
            f"useful={ratio:6.2%}"
        )
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default=str(REPORT_DIR))
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    n_fail = 0
    for a in archs:
        for s in shapes:
            try:
                rep = analyze(a, s)
            except Exception as e:  # reported per cell, and in the exit code
                n_fail += 1
                rep = {"arch": a, "shape": s, "status": "failed", "error": str(e)}
                print(f"[roofline] {a:28s} {s:12s} FAIL {e}")
            (outdir / f"{a}_{s}.json").write_text(json.dumps(rep, indent=2))
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
