"""Pallas TPU kernel for fused uplink screening (the robustness layer).

ONE pass over the ``(m, width)`` uplink arena emits, per client row,

  * a finite flag -- every entry of the row is finite, and
  * the squared deviation ``sum over the FINITE entries of (u_i - ref)^2``

so the server can demote non-finite or norm-outlier uplinks to silent
without a second read of the buffer (``core.faults.screen_keep``).  The
deviation is taken against the downlink reference rather than as a plain
norm: a sign-flipped uplink is norm-invariant, but its deviation from x_s
is ~ ``||2 x_s||``.  Non-finite entries are excluded from the deviation
(the flag already demotes those rows), so ``sq`` is always finite and
comparable across backends.

Layout: grid ``(m, rows_p // block)`` with the width blocks INNERMOST, so
each client's two per-lane accumulator rows -- ``(1, 1, LANES)`` f32 blocks
of the tiny ``(m, 1, LANES)`` outputs -- are revisited across the row's width
blocks and stay VMEM-resident (the same revisited-output accumulation
contract as ``neighbor_reduce``).  The cheap cross-lane finish (sum / min
over LANES) runs on the per-lane partials outside the kernel.

``ref`` is either the ``(width,)`` server downlink row (centralised rounds)
or an ``(m, width)`` per-row reference (graph rounds screen each node's
transmitted ``x_ref`` against that node's own previous carry).  Zero
padding -- the arena tail rows and the ``rows_p - rows`` tile pad, zero on
BOTH operands by the arena invariant -- contributes zero deviation and a
finite flag, so padded and unpadded widths screen identically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import LANES, assert_vmem_budget
from repro.kernels.round_tail import _resolve_block, _tile


def _screen_kernel(u_ref, r_ref, sq_ref, fin_ref, *, per_row: bool):
    j = pl.program_id(1)
    u = u_ref[0].astype(jnp.float32)  # (br, LANES)
    r = (r_ref[0] if per_row else r_ref[...]).astype(jnp.float32)
    fin_e = jnp.isfinite(u)
    d = jnp.where(fin_e, u - r, 0.0)
    sq = jnp.sum(d * d, axis=0, keepdims=True)  # (1, LANES) per-lane partial
    fin = jnp.min(jnp.where(fin_e, 1.0, 0.0), axis=0, keepdims=True)

    @pl.when(j == 0)
    def _init():
        sq_ref[0] = sq
        fin_ref[0] = fin

    @pl.when(j != 0)
    def _acc():
        sq_ref[0] = sq_ref[0] + sq
        fin_ref[0] = jnp.minimum(fin_ref[0], fin)


def screen_uplink_pallas(u, ref, *, block=None, interpret: bool = False):
    """u: (m, width) uplink arena; ref: (width,) broadcast downlink row or
    (m, width) per-row reference.  Returns ``(finite (m,) bool, sq (m,) f32)``.
    """
    m, w = u.shape
    per_row = ref.ndim == 2
    pad = (-w) % LANES
    if pad:
        # zero on BOTH operands: zero deviation, finite flag -- identical
        # screen to the unpadded width (arena callers are always aligned)
        u = jnp.pad(u, ((0, 0), (0, pad)))
        ref = jnp.pad(ref, ((0, 0), (0, pad)) if per_row else ((0, pad),))
        w += pad
    br = _resolve_block(block, w // LANES)
    assert_vmem_budget(2, br)
    ut, _, rows_p = _tile(u, br)
    rt, _, _ = _tile(ref, br)
    client_bs = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    ref_bs = (client_bs if per_row
              else pl.BlockSpec((br, LANES), lambda i, j: (j, 0)))
    acc_bs = pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, 0))
    sq, fin = pl.pallas_call(
        functools.partial(_screen_kernel, per_row=per_row),
        grid=(m, rows_p // br),  # width blocks innermost: accumulators stay hot
        in_specs=[client_bs, ref_bs],
        out_specs=(acc_bs, acc_bs),
        out_shape=(jax.ShapeDtypeStruct((m, 1, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1, LANES), jnp.float32)),
        interpret=interpret,
    )(ut, rt)
    return jnp.min(fin, axis=(1, 2)) > 0.5, jnp.sum(sq, axis=(1, 2))
