"""Pallas TPU kernel for the fused fixed-point residual norms (the
early-termination criterion, ``core.autotune``).

ONE pass over the ``(m, width)`` client-state arena and its previous-round
snapshot emits, per client row,

  * ``dx2_i = ||x_i - x_prev_i||^2``   (the fixed-point residual), and
  * ``x2_i  = ||x_i||^2``              (the normaliser),

so the host driver can evaluate pfb-clean's relative stopping rule
``||x - x_prev|| / ||x|| < tol`` without a second read of either buffer.
The per-client split (rather than a single server scalar) keeps the kernel
reusable for cohort paths -- the caller reduces over whichever rows
participated.

Layout: grid ``(m, rows_p // block)`` with the width blocks INNERMOST, so
each client's two per-lane accumulator rows -- ``(1, 1, LANES)`` f32 blocks
of the tiny ``(m, 1, LANES)`` outputs -- are revisited across the row's width
blocks and stay VMEM-resident (the same revisited-output accumulation
contract as ``screen`` / ``neighbor_reduce``).  The cheap cross-lane finish
(sum over LANES) runs on the per-lane partials outside the kernel.

Zero padding -- the arena tail rows and the ``rows_p - rows`` tile pad,
zero on BOTH operands by the arena invariant -- contributes zero to both
sums, so padded and unpadded widths agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import LANES, assert_vmem_budget
from repro.kernels.round_tail import _resolve_block, _tile


def _residual_kernel(x_ref, p_ref, dx_ref, x2_ref):
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)  # (br, LANES)
    p = p_ref[0].astype(jnp.float32)
    d = x - p
    dx = jnp.sum(d * d, axis=0, keepdims=True)  # (1, LANES) per-lane partial
    x2 = jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(j == 0)
    def _init():
        dx_ref[0] = dx
        x2_ref[0] = x2

    @pl.when(j != 0)
    def _acc():
        dx_ref[0] = dx_ref[0] + dx
        x2_ref[0] = x2_ref[0] + x2


def residual_norm_pallas(x, x_prev, *, block=None, interpret: bool = False):
    """x, x_prev: (m, width).  Returns ``(dx2 (m,) f32, x2 (m,) f32)`` --
    per-client ``||x - x_prev||^2`` and ``||x||^2`` in one fused pass."""
    m, w = x.shape
    assert x_prev.shape == (m, w), (x.shape, x_prev.shape)
    pad = (-w) % LANES
    if pad:
        # zero on BOTH operands: zero contribution to both sums -- identical
        # residual to the unpadded width (arena callers are always aligned)
        x = jnp.pad(x, ((0, 0), (0, pad)))
        x_prev = jnp.pad(x_prev, ((0, 0), (0, pad)))
        w += pad
    br = _resolve_block(block, w // LANES)
    assert_vmem_budget(2, br)
    xt, _, rows_p = _tile(x, br)
    pt, _, _ = _tile(x_prev, br)
    client_bs = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    acc_bs = pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, 0))
    dx, x2 = pl.pallas_call(
        _residual_kernel,
        grid=(m, rows_p // br),  # width blocks innermost: accumulators stay hot
        in_specs=[client_bs, client_bs],
        out_specs=(acc_bs, acc_bs),
        out_shape=(jax.ShapeDtypeStruct((m, 1, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1, LANES), jnp.float32)),
        interpret=interpret,
    )(xt, pt)
    return jnp.sum(dx, axis=(1, 2)), jnp.sum(x2, axis=(1, 2))
