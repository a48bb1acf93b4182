"""Pallas TPU kernel for the fused stale-uplink admission mix (ISSUE 7).

ONE pass over the uplink, cache, and stale-buffer arenas emits, per client
row,

  * the MIXED contribution row that enters the server mean:
    ``base = fresh ? uplink : cache`` (today's masked select, bit-exact),
    then ``base + w * (stale - base)`` on the rows whose stale uplink is
    admitted this round (``w = gamma**lateness > 0``), and
  * the updated stale buffer: delayed clients' uplink rows stored in their
    (free) slot, every other slot carried through.

All the per-client admission bookkeeping (occupancy, age, lateness,
deadline) is layout-independent integer math done OUTSIDE the kernel
(``core.staleness``); the kernel only consumes three per-client scalars --
``fresh``, ``store``, ``w`` -- broadcast to ``(m, 1, LANES)`` f32 rows
(``round_tail.client_row``) so each grid step reads them as ``(1, LANES)``
VMEM rows and broadcasts them against the ``(block, LANES)`` data tiles (no
SMEM scalar plumbing).

The admitted-mix guard ``where(w > 0, base + w*(stale - base), base)`` is
load-bearing for the synchronous collapse: at ``w == 0`` the select returns
``base`` BITWISE (no ``-0.0 + 0.0`` sign flips, no ``0 * inf`` NaNs from a
non-finite buffered row), which is what makes ``max_staleness=0`` collapse
to today's masked round exactly.

Layout: grid ``(m, rows_p // block)`` over the ``(m, rows_p, LANES)`` tiled
views; outputs are written block-by-block (no accumulation).  ``cache`` is
either the ``(width,)`` broadcast server row (SCAFFOLD's zero-delta
baseline) or the ``(m, width)`` per-client ``u_hat`` cache.  Zero padding
is preserved: pad columns are zero on every operand, and both the select
and the mix map 0 -> 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import LANES, assert_vmem_budget
from repro.kernels.round_tail import (
    CLIENT_ROW_BS, _resolve_block, _tile, client_row,
)


def _stale_mix_kernel(u_ref, c_ref, b_ref, f_ref, s_ref, w_ref,
                      mix_ref, bo_ref, *, per_row: bool):
    u = u_ref[0].astype(jnp.float32)  # (br, LANES)
    c = (c_ref[0] if per_row else c_ref[...]).astype(jnp.float32)
    buf = b_ref[0].astype(jnp.float32)
    fresh = f_ref[0]  # (1, LANES) constant row, broadcasts over br
    w = w_ref[0]
    base = jnp.where(fresh > 0.5, u, c)
    mix = jnp.where(w > 0.0, base + w * (buf - base), base)
    mix_ref[0] = mix.astype(mix_ref.dtype)
    bo_ref[0] = jnp.where(s_ref[0] > 0.5, u, buf).astype(bo_ref.dtype)


def stale_mix_pallas(uplink, cache, buf, fresh, store, w, *, block=None,
                     interpret: bool = False):
    """uplink/buf: (m, width) arenas; cache: (width,) broadcast row or
    (m, width); fresh/store: (m,) bool; w: (m,) f32 admission weights.
    Returns ``(mixed (m, width), buf_new (m, width))``."""
    m, width = uplink.shape
    per_row = cache.ndim == 2
    pad = (-width) % LANES
    if pad:
        # zero on every operand: the select and the mix both map 0 -> 0,
        # so padded and unpadded widths mix identically
        uplink = jnp.pad(uplink, ((0, 0), (0, pad)))
        buf = jnp.pad(buf, ((0, 0), (0, pad)))
        cache = jnp.pad(cache, ((0, 0), (0, pad)) if per_row else ((0, pad),))
        width += pad
    br = _resolve_block(block, width // LANES)
    assert_vmem_budget(5, br)
    ut, _, rows_p = _tile(uplink, br)
    ct, _, _ = _tile(cache, br)
    bt, _, _ = _tile(buf, br)
    client_bs = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    cache_bs = (client_bs if per_row
                else pl.BlockSpec((br, LANES), lambda i, j: (j, 0)))
    mixed, buf_new = pl.pallas_call(
        functools.partial(_stale_mix_kernel, per_row=per_row),
        grid=(m, rows_p // br),
        in_specs=[client_bs, cache_bs, client_bs,
                  CLIENT_ROW_BS, CLIENT_ROW_BS, CLIENT_ROW_BS],
        out_specs=(client_bs, client_bs),
        out_shape=(jax.ShapeDtypeStruct((m, rows_p, LANES), uplink.dtype),
                   jax.ShapeDtypeStruct((m, rows_p, LANES), buf.dtype)),
        interpret=interpret,
    )(ut, ct, bt, client_row(fresh), client_row(store), client_row(w))
    w_out = width - pad
    untile = lambda t: t.reshape(m, rows_p * LANES)[:, :w_out]  # noqa: E731
    return untile(mixed), untile(buf_new)
