"""Readings of a GPDMM state and the numbers that decide ``correct``.

A reading is a norm per parameter leaf: the server's change from the
starting point x0, each client's change, and each client's dual.  Program
and reference are compared by the gap between their norms, leaf by leaf,
against the reference's norm of that leaf or of the median leaf, whichever
is larger; the worst leaf counts.  Leaves whose first gradient in the
reference is under a thousandth of the median leaf's are left out: they
move by rounding alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
GRAD_FLOOR = 1e-3


def leaf_norms(tree, lead: int = 0):
    """Norm of every leaf over all but its ``lead`` leading dims, stacked on
    a last axis in leaf order: shape lead_dims + (n_leaves,)."""
    out = []
    for a in jax.tree.leaves(tree):
        a = a.astype(F32)
        out.append(jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[:lead] + (-1,))),
                                    axis=-1)))
    return jnp.stack(out, axis=-1)


def readings(x0, x_s, x_c, lam):
    """x0, x_s: parameter pytrees; x_c, lam: the same stacked over clients."""
    sub = lambda a, b: a.astype(F32) - b.astype(F32)  # noqa: E731
    return {
        "update": leaf_norms(jax.tree.map(sub, x_s, x0)),
        "client": leaf_norms(jax.tree.map(lambda c, b: sub(c, b[None]), x_c, x0), 1),
        "dual": leaf_norms(lam, 1),
    }


def leaf_gap(prog, ref, keep) -> float:
    """Worst gap |prog - ref| over the kept leaves (last axis), each against
    max(ref, median over the kept leaves of its row)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    prog, ref = prog[..., keep], ref[..., keep]
    floor = np.median(ref, axis=-1, keepdims=True)
    scale = np.maximum(ref, floor)
    return float(np.max(np.abs(prog - ref) / np.maximum(scale, 1e-30)))


def kept_leaves(grad0) -> np.ndarray:
    g = np.asarray(grad0, np.float64)
    return g >= GRAD_FLOOR * np.median(g)


def numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"rounds": [readings after round 1, 2, ...],
    "drift": [client drift of each round]}; ``ref`` also holds "grad0", the
    reference's first gradient norm per leaf (mean over clients)."""
    keep = kept_leaves(ref["grad0"])
    pr, rr = prog["rounds"], ref["rounds"]
    pd, rd = np.asarray(prog["drift"], np.float64), np.asarray(ref["drift"], np.float64)
    return {
        "drift_gap": float(np.max(np.abs(pd - rd) / rd)),
        "update1_gap": leaf_gap(pr[0]["update"], rr[0]["update"], keep),
        "update3_gap": leaf_gap(pr[-1]["update"], rr[-1]["update"], keep),
        "client3_gap": leaf_gap(pr[-1]["client"], rr[-1]["client"], keep),
        "dual3_gap": leaf_gap(pr[-1]["dual"], rr[-1]["dual"], keep),
    }
