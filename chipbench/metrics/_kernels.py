"""Shared by the kernel roofline readers: find a Pallas kernel's ops in the
trace and divide the least time its bytes need by the time they took.

A round kernel is known by its operands: a Pallas call
(``custom_call_target="tpu_custom_call"``) over per-client blocks
``[m, rows, 128]`` and the server row ``[rows, 128]``.  The eq. (20) update
reads three client arrays (x, g, lam) and the server row; the uplink reads
two (xbar, lam) and the server row.
"""
from __future__ import annotations

import re

from chipbench import trace

KERNELS = {"fused_update_arena": (3, 1), "round_tail": (2, 1)}
_CALL = re.compile(r"custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


def operands(long_name: str):
    """(client operands, server operands) of a Pallas call, else None."""
    m = _CALL.search(long_name)
    if m is None:
        return None
    ranks = [len(s.split(",")) for s in _SHAPE.findall(m.group(1))]
    return ranks.count(3), ranks.count(2)


def matcher(kernel: str):
    want = KERNELS[kernel]

    def match(name, args):
        return operands(args.get("long_name", "")) == want

    return match


def roofline(ctx, kernel: str):
    """Least HBM time of the kernel's calls in the window over their device
    time, in %; None where the trace holds no such call."""
    match = matcher(kernel)
    secs = trace.device_seconds(ctx["trace"], match, ctx["window"])
    calls = trace.count_ops(ctx["trace"], match, ctx["window"])
    if secs <= 0 or calls == 0:
        return None
    need = calls * ctx["counts"]["kernels"][kernel]["bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / secs
