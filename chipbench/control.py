"""Readings that set the limits of ``correct``, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,... [--control-seeds 3]

For every seed: the program's checked rounds against the plain reference
(the lower readings).  For the first ``--control-seeds`` seeds also the
control, the reference computed in the precision next below the one the
configuration states, and the reference with half of every batch left out,
each against the reference (the upper readings).  A state left unchanged
reads 1 on every gap by construction and needs no run.

Prints one JSON line per seed and reading, then the largest program reading
and the smallest control and fault readings of every number, and writes the
same to ``chipbench_out/control.<cell>.json``.  The benchmark's own
runs do not run this.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import traceback

REPO = pathlib.Path(__file__).resolve().parents[1]


def emit(row) -> None:
    print(json.dumps({"seed": row[0], "kind": row[1], "numbers": row[2]}), flush=True)


def readings(workload: str, seeds, control_seeds: int, root=REPO, check=True):
    """[(seed, kind, numbers)] with kind "program", "control" or
    "half_batch"."""
    import jax

    from chipbench import compare, harness
    from chipbench.objectives import common

    c = harness.cell(workload, root)
    devices = jax.devices()
    if check:
        harness.check_devices(devices, c["workload"]["chips"],
                              harness.load_json(harness.HERE / "peaks.json"))
    devices = devices[:c["workload"]["chips"]]
    from repro.launch import compile_cache

    compile_cache.enable()
    mod = harness.load_module(c["objective"])
    dtype = c["config"]["dtype"]
    out = []
    for i, seed in enumerate(seeds):
        obj = mod.Objective(c["config"], c["traffic"], devices, seed)
        obj.setup()
        prog = {"rounds": [], "drift": []}
        for _ in range(obj.checked):
            met = jax.device_get(obj.step())
            prog["drift"].append(float(met["client_drift"]))
            prog["rounds"].append(obj.readings())
        obj.close()
        ref = obj.reference(dtype)
        rows = [(seed, "program", compare.numbers(prog, ref))]
        emit(rows[-1])
        if i < control_seeds:
            for kind, store, fault in (("control", common.LOWER[dtype], None),
                                       ("half_batch", dtype, "half_batch")):
                try:
                    rows.append((seed, kind, compare.numbers(
                        obj.reference(store, fault=fault), ref)))
                except Exception:  # a control that crashes has failed
                    print(f"[control] {kind} on seed {seed} gave no number:",
                          file=sys.stderr)
                    traceback.print_exc()
                    continue
                emit(rows[-1])
        out += rows
        del obj
    return out


def summary(rows) -> dict:
    s = {}
    for kind, pick in (("program", max), ("control", min), ("half_batch", min)):
        sel = [r[2] for r in rows if r[1] == kind]
        if sel:
            s[kind] = {k: pick(n[k] for n in sel) for k in sel[0]}
    return s


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from chipbench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        rows = readings(args.workload, seeds, args.control_seeds)
    except harness.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    s = summary(rows)
    print(json.dumps({"summary": s}))
    out = REPO / "chipbench_out" / f"control.{args.workload}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"rows": rows, "summary": s}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
