"""Parses compiled/lowered HLO text for collective traffic.

``cost_analysis()`` does not expose collective bytes, so we sum operand/result
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute in the (SPMD-partitioned) module.  Ops inside ``while``
bodies appear once in the text; callers that scan over layers extrapolate via
the 1-unit/2-unit diff (see launch.roofline).

CPU-backend caveat (recorded in EXPERIMENTS.md): XLA:CPU *promotes* bf16
all-reduces to f32 -- the HLO shows ``convert(bf16 dot) -> f32 all-reduce``
with a ``to_apply=%add.N.clone_promoted`` reducer.  On TPU those collectives
stay bf16, so parsed byte totals are an UPPER bound (up to 2x) for
bf16-activation models; A/B deltas remain comparable since both sides are
promoted identically.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# shape tokens like f32[16,128]{1,0} or bf16[2,4096] or pred[]
_SHAPE_RE = re.compile(r"\b(pred|[sub]\d+|bf16|f16|f32|f64)\[([\d,]*)\]")
# an HLO instruction: "%name = <result-shape-or-tuple> opcode(...)"
_INSTR_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[\w\[\]{},:#\s]*?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Returns {op_kind: {"count": n, "bytes": result_bytes_total}} plus a
    "total" entry.  Bytes are the result-shape sizes (for all-gather that is
    the gathered size; for all-reduce the tensor size -- a reasonable proxy
    for per-device link traffic in a ring implementation)."""
    out: dict = defaultdict(lambda: {"count": 0, "bytes": 0})
    for m in _INSTR_RE.finditer(hlo_text):
        result_shapes, kind = m.group(1), m.group(2)
        # skip the -done halves of async pairs (counted at -start)
        if hlo_text[m.start():m.end()].rstrip("(").endswith("-done"):
            continue
        b = _shape_bytes(result_shapes)
        out[kind]["count"] += 1
        out[kind]["bytes"] += b
    total = {"count": sum(v["count"] for v in out.values()),
             "bytes": sum(v["bytes"] for v in out.values())}
    result = dict(out)
    result["total"] = total
    return result


def flops_and_bytes(compiled) -> tuple[float, float]:
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
    }
