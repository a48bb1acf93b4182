"""Pallas TPU kernels for the fused GPDMM/AGPDMM round tail over the flat
client-state arena (``core.arena``).

After the K inner steps, the pytree round runs ~6 separate per-leaf passes
(``lam_is``, uplink, EF21 sub/quantise/add, participation select, server
mean, ``lam_s_new``), each re-reading the full ``(m, params)`` state from
HBM.  On the arena the same math becomes three fused kernels:

  * ``round_tail_pallas``   -- lam_is = rho (x_s - x_ref) - lam_s  and the
                               uplink u = x_ref - lam_is / rho in ONE pass:
                               3 reads + 2 writes instead of ~4 passes; with
                               a ``scale`` it reads the running sum of the
                               inner iterates and forms x_bar in VMEM.
  * ``ef21_*``              -- EF21 quantise-delta in TWO passes: a rowwise
                               max-abs reduction (the only full read of
                               u/u_hat) + the quantise-dequantise-integrate
                               apply, instead of the tree_sub -> per-leaf
                               _qdq (2 passes) -> tree_add chain.
  * ``fused_update_arena_pallas`` -- the eq. (20) inner step over the whole
                               packed buffer with the server row broadcast
                               in-kernel, so the K-step scan issues ONE
                               pallas_call per step instead of one per leaf;
                               ``fused_update_arena_sum_pallas`` carries the
                               running sum of the iterates beside it.

The elementwise kernels (eq. (20), the uplink, the dual refresh, SCAFFOLD's
control variate) read the ``(m, width)`` arena as it lies, in ``(bm, bw)``
blocks (``_Blocks``); fewer clients than a sublane tile make one block of
all m rows.  ``fused_update_client_pallas`` steps one client's row of the
arena in place, for rounds that apply each client's gradient as soon as it
is made.  In the EF21 kernels a client row is tiled as
``(rows = width // 128, 128)`` and padded to whole blocks of rows; the
arena pads every leaf to a 128-lane multiple, so tiles never straddle
leaves and the EF21 per-(client, leaf) quantisation scale is a static
row-segment reduction (same semantics as the per-leaf pytree path).

Server-row operands use a broadcast index map (the same width block for
every client block) -- the (m, width) broadcast is never materialised in
HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_update import (
    BLOCK_ROWS, LANES, RELAYOUT, assert_vmem_budget, ceil_to as _ceil_to, eq20,
)
from repro.kernels.ops import LAYOUT


def _tile(arr, block: int):
    """(m, width) or (width,) -> (..., rows_p, LANES) with rows_p a multiple
    of ``block`` (zero-padded)."""
    w = arr.shape[-1]
    assert w % LANES == 0, f"arena width {w} not a multiple of {LANES}"
    rows = w // LANES
    rows_p = _ceil_to(rows, block)
    with jax.named_scope(RELAYOUT):
        t = arr.reshape(arr.shape[:-1] + (rows, LANES))
        if rows_p != rows:
            pad = [(0, 0)] * (t.ndim - 2) + [(0, rows_p - rows), (0, 0)]
            t = jnp.pad(t, pad)
    return t, rows, rows_p


def _untile(t, width: int, lead):
    with jax.named_scope(RELAYOUT):
        return t.reshape(lead + (-1,))[..., :width]


def client_row(v):
    """(m,) per-client scalars -> (m, 1, LANES) f32 constant rows.  The unit
    middle axis makes the (1, 1, LANES) block's last two dims equal the
    array's, which the TPU lowering requires of a block that is not
    (8, 128)-aligned; the kernel reads a (1, LANES) row that broadcasts over
    its (block, LANES) data tile."""
    m = v.shape[0]
    with jax.named_scope(RELAYOUT):
        return jnp.broadcast_to(v.astype(jnp.float32)[:, None, None], (m, 1, LANES))


# client i's constant row on an (m, width-blocks) grid
CLIENT_ROW_BS = pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, 0))


def _resolve_block(block, rows: int) -> int:
    block = block or BLOCK_ROWS
    # clamp to the (8-sublane-aligned) problem size so small paper-scale
    # problems don't pad a 1-row state out to a full default block
    return min(block, max(8, _ceil_to(rows, 8)))


def _sublanes(dtype) -> int:
    """Rows of one (rows, LANES) VMEM tile of ``dtype``: 8 for 32-bit
    values, 16 for 16-bit."""
    return 32 // jnp.dtype(dtype).itemsize


# A flat-path block holds up to FLAT_BLOCK_ROWS clients and about
# FLAT_BLOCK_BYTES of f32 (the kernels' working precision).  On a v5e the
# eq. (20) kernel over the femnist.full arena ran fastest at (128, 2048):
# 3.89 ms a call against 4.02 at (8, 16256) and 3.97 at (256, 1024); 2 MiB
# blocks overflow VMEM (PERF.md, the (bm, bw) sweep).
FLAT_BLOCK_ROWS = 128
FLAT_BLOCK_BYTES = 1024 * 1024


def _flat_blocks(m: int, w: int, dtype, n_arrays: int, block=None):
    """``(bm, bw)`` blocks of the client arena as it lies, ``(m, w)`` seen as
    ``(1, m, w)``.  ``block``: client rows per block (default
    ``FLAT_BLOCK_ROWS``), rounded up to the sublane tile and cut to the
    whole tiles m holds; fewer clients than a tile make one block of all m
    rows (a block dim equal to the array's is legal at any size).  ``bw``
    splits the width evenly into 128-lane multiples of about
    ``FLAT_BLOCK_BYTES`` of f32 per block, its rows counted as the VMEM
    tile pads them."""
    sub = _sublanes(dtype)
    bm = m if m < sub else min(_ceil_to(block or FLAT_BLOCK_ROWS, sub), m // sub * sub)
    rows = _ceil_to(bm, 8)
    n_w = pl.cdiv(w, max(LANES, FLAT_BLOCK_BYTES // (rows * 4)))
    bw = _ceil_to(pl.cdiv(w, n_w), LANES)
    assert_vmem_budget(n_arrays, rows * bw // LANES)
    return bm, bw


class _Blocks:
    """Operand layout of one elementwise arena kernel: ``(m, width)`` client
    buffers, ``(width,)`` server rows and ``(m,)`` per-client scalars in,
    client buffers out.  Client buffers enter as ``x[None]``, ``(1, m,
    width)``, a bitcast, in ``(1, bm, bw)`` blocks, and server rows as
    ``(1, width)`` in ``(1, bw)`` blocks; the grid runs clients innermost,
    so a server block stays resident across consecutive steps, and Pallas
    masks the ragged edge blocks.  ``ops.LAYOUT`` records each kernel's
    ``(bm, bw)`` at its last trace."""

    def __init__(self, name, m, w, dtype, n_arrays, block):
        assert w % LANES == 0, f"arena width {w} not a multiple of {LANES}"
        self.m = m
        bm, bw = _flat_blocks(m, w, dtype, n_arrays, block)
        self.grid = (pl.cdiv(w, bw), pl.cdiv(m, bm))
        self.client = pl.BlockSpec((1, bm, bw), lambda j, i: (0, i, j))
        self.server = pl.BlockSpec((1, bw), lambda j, i: (0, j))
        self.row = pl.BlockSpec((1, bm, LANES), lambda j, i: (0, i, 0))
        self.out_lead = (1, m, w)
        LAYOUT[name] = (bm, bw)

    @staticmethod
    def clients(a):
        return a[None]

    servers = clients

    def rows(self, v):
        """(m,) per-client scalars as f32 rows the kernel reads as
        ``ref[0][:, :1]``: one value per client row of its block."""
        with jax.named_scope(RELAYOUT):
            return jnp.broadcast_to(v.astype(jnp.float32)[None, :, None], (1, self.m, LANES))

    def out_shape(self, dtype):
        return jax.ShapeDtypeStruct(self.out_lead, dtype)

    @staticmethod
    def back(out):
        return out[0]


# ---------------------------------------------------------------------------
# (a) lam_is + uplink in one pass
# ---------------------------------------------------------------------------

def _x_ref(xr_ref, scale):
    """The uplink's x_ref block in f32: as it lies, or x_bar = sum * scale
    formed from the running sum of iterates and rounded to the state dtype,
    as an XLA multiply by the scale in that dtype would round it."""
    xr = xr_ref[0].astype(jnp.float32)
    if scale is None:
        return xr
    return (xr * scale).astype(xr_ref.dtype).astype(jnp.float32)


def _round_tail_kernel(xr_ref, lam_ref, xs_ref, lam_is_ref, up_ref, *, rho: float,
                       scale):
    xr = _x_ref(xr_ref, scale)
    lam = lam_ref[0].astype(jnp.float32)
    xs = xs_ref[...].astype(jnp.float32)
    lam_is = rho * (xs - xr) - lam
    lam_is_ref[0] = lam_is.astype(lam_is_ref.dtype)
    up_ref[0] = (xr - lam_is / rho).astype(up_ref.dtype)


def _uplink_kernel(xr_ref, lam_ref, xs_ref, up_ref, *, rho: float, scale):
    # uplink only (lam_is algebraically eliminated): u = 2 x_ref - x_s + lam/rho
    xr = _x_ref(xr_ref, scale)
    lam = lam_ref[0].astype(jnp.float32)
    xs = xs_ref[...].astype(jnp.float32)
    up_ref[0] = (xr - (rho * (xs - xr) - lam) / rho).astype(up_ref.dtype)


def _dtype_scale(scale, dtype):
    """``scale`` as an f32 Python float, rounded to ``dtype`` first: the
    factor an XLA multiply of a ``dtype`` array by the Python scalar uses.
    None passes through."""
    if scale is None:
        return None
    return float(np.asarray(scale, jnp.dtype(dtype)).astype(np.float32))


def round_tail_pallas(x_ref, lam_s, x_s, rho, *, with_lam_is: bool = True,
                      scale=None, block=None, interpret: bool = False):
    """x_ref, lam_s: (m, width); x_s: (width,) server row.  Returns
    (lam_is, uplink), both (m, width).  ``with_lam_is=False`` (the training
    hot path: both callers discard lam_is outside traces) skips the second
    output entirely -- 3 reads + 1 write -- and returns (None, uplink).
    ``scale``: None reads x_ref as it is; a static factor reads the running
    sum of the inner iterates there and forms x_bar = sum * scale in VMEM,
    so no pass of its own materialises the average."""
    m, w = x_ref.shape
    lay = _Blocks("round_tail", m, w, x_ref.dtype, 5 if with_lam_is else 4, block)
    args = (lay.clients(x_ref), lay.clients(lam_s), lay.servers(x_s))
    in_specs = [lay.client, lay.client, lay.server]
    out_sds = lay.out_shape(x_ref.dtype)
    kw = dict(rho=float(rho), scale=_dtype_scale(scale, x_ref.dtype))
    if not with_lam_is:
        up = pl.pallas_call(
            functools.partial(_uplink_kernel, **kw),
            name="round_tail",
            grid=lay.grid,
            in_specs=in_specs,
            out_specs=lay.client,
            out_shape=out_sds,
            interpret=interpret,
        )(*args)
        return None, lay.back(up)
    lam_is, up = pl.pallas_call(
        functools.partial(_round_tail_kernel, **kw),
        name="round_tail",
        grid=lay.grid,
        in_specs=in_specs,
        out_specs=(lay.client, lay.client),
        out_shape=(out_sds, out_sds),
        interpret=interpret,
    )(*args)
    return lay.back(lam_is), lay.back(up)


# ---------------------------------------------------------------------------
# SCAFFOLD control-variate refresh (the per-client half of the round tail;
# the two server all-reduces stay jnp means -- they ARE the collectives)
# ---------------------------------------------------------------------------

def _scaffold_cv_kernel(ci_ref, xk_ref, c_ref, xs_ref, o_ref, *, alpha: float):
    f32 = jnp.float32
    ci = ci_ref[0].astype(f32)
    xk = xk_ref[0].astype(f32)
    c = c_ref[...].astype(f32)
    xs = xs_ref[...].astype(f32)
    o_ref[0] = (ci - c + alpha * (xs - xk)).astype(o_ref.dtype)


def _scaffold_cv_kernel_valpha(ci_ref, xk_ref, c_ref, xs_ref, a_ref, o_ref):
    # per-client alpha = 1/(K eta_i) read as one value per client row of
    # the block (core.autotune's per-client stepsizes)
    f32 = jnp.float32
    ci = ci_ref[0].astype(f32)
    xk = xk_ref[0].astype(f32)
    c = c_ref[...].astype(f32)
    xs = xs_ref[...].astype(f32)
    o_ref[0] = (ci - c + a_ref[0][:, :1] * (xs - xk)).astype(o_ref.dtype)


def scaffold_cv_pallas(c_i, x_K, c_s, x_s, alpha, *, block=None, interpret: bool = False):
    """SCAFFOLD eq. (30) control-variate update in ONE pass:

        c_i' = c_i - c + (x_s - x_K) * alpha        (alpha = 1/(K eta))

    c_i, x_K: (m, width) client buffers; c_s, x_s: (width,) server rows
    (broadcast in-kernel, never materialised at (m, width)).  ``alpha``:
    scalar (baked constant) or (m,) per-client values (auto-eta) riding a
    broadcast row operand.  2 client reads + 1 write instead of the ~5-pass
    per-leaf tmap chain."""
    m, w = c_i.shape
    lay = _Blocks("scaffold_cv", m, w, c_i.dtype, 5, block)
    args = [lay.clients(c_i), lay.clients(x_K), lay.servers(c_s), lay.servers(x_s)]
    in_specs = [lay.client, lay.client, lay.server, lay.server]
    if jnp.ndim(alpha) > 0:
        assert alpha.shape == (m,), alpha.shape
        args.append(lay.rows(alpha))
        in_specs.append(lay.row)
        kernel = _scaffold_cv_kernel_valpha
    else:
        kernel = functools.partial(_scaffold_cv_kernel, alpha=float(alpha))
    out = pl.pallas_call(
        kernel,
        name="scaffold_cv",
        grid=lay.grid,
        in_specs=in_specs,
        out_specs=lay.client,
        out_shape=lay.out_shape(c_i.dtype),
        interpret=interpret,
    )(*args)
    return lay.back(out)


# ---------------------------------------------------------------------------
# lam_s' = rho (u - x_s') -- the post-all-reduce dual refresh
# ---------------------------------------------------------------------------

def _dual_kernel(u_ref, xs_ref, o_ref, *, rho: float):
    u = u_ref[0].astype(jnp.float32)
    xs = xs_ref[...].astype(jnp.float32)
    o_ref[0] = (rho * (u - xs)).astype(o_ref.dtype)


def dual_from_uplink_pallas(uplink, x_s, rho, *, block=None, interpret: bool = False):
    """uplink: (m, width); x_s: (width,).  Returns lam_s' = rho (u - x_s)."""
    m, w = uplink.shape
    lay = _Blocks("dual_from_uplink", m, w, uplink.dtype, 3, block)
    out = pl.pallas_call(
        functools.partial(_dual_kernel, rho=float(rho)),
        name="dual_from_uplink",
        grid=lay.grid,
        in_specs=[lay.client, lay.server],
        out_specs=lay.client,
        out_shape=lay.out_shape(uplink.dtype),
        interpret=interpret,
    )(lay.clients(uplink), lay.servers(x_s))
    return lay.back(out)


# ---------------------------------------------------------------------------
# (b) fused EF21: rowwise max-abs reduce + quantise-dequantise-integrate
# ---------------------------------------------------------------------------

def _ef21_block(block, rows: int) -> int:
    """Row block for the EF21 kernels: their per-row scale operand lies
    along lanes as a (1, 1, block) block of an (m, 1, rows_p) array, so a
    block that does not cover all rows must be a multiple of LANES."""
    br = _resolve_block(block, rows)
    return br if br >= rows else _ceil_to(br, LANES)


# one client's (1, 1, block) slice of the (m, 1, rows_p) per-row scale array
_ROW_SCALE_BS = lambda br: pl.BlockSpec((1, 1, br), lambda i, j: (i, 0, j))  # noqa: E731


def _rowmax_kernel(u_ref, uh_ref, o_ref):
    d = u_ref[0].astype(jnp.float32) - uh_ref[0].astype(jnp.float32)
    o_ref[0] = jnp.max(jnp.abs(d), axis=-1)[None, :]


def ef21_rowmax_pallas(u, u_hat, *, block=None, interpret: bool = False):
    """Per-(client, 128-lane row) max-abs of (u - u_hat): (m, rows) f32.
    The only full-size read of the reduction pass."""
    m, w = u.shape
    rows = w // LANES
    br = _ef21_block(block, rows)
    assert_vmem_budget(2, br)
    ut, _, rows_p = _tile(u, br)
    ht, _, _ = _tile(u_hat, br)
    out = pl.pallas_call(
        _rowmax_kernel,
        name="ef21_rowmax",
        grid=(m, rows_p // br),
        in_specs=[
            pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=_ROW_SCALE_BS(br),
        out_shape=jax.ShapeDtypeStruct((m, 1, rows_p), jnp.float32),
        interpret=interpret,
    )(ut, ht)
    with jax.named_scope(RELAYOUT):
        return out[:, 0, :rows]


def _qdq_kernel(u_ref, uh_ref, scale_ref, o_ref, *, lo: float):
    u = u_ref[0].astype(jnp.float32)
    uh = uh_ref[0].astype(jnp.float32)
    s = scale_ref[0, 0][:, None]  # (br, 1) broadcast over lanes
    q = jnp.clip(jnp.round((u - uh) / s), -lo, lo)
    o_ref[0] = (uh + q * s).astype(o_ref.dtype)


def ef21_apply_pallas(u, u_hat, row_scales, bits: int, *, block=None, interpret: bool = False):
    """Integrated server view u_hat' = u_hat + qdq(u - u_hat) in one pass.
    ``row_scales``: (m, rows) f32 per-128-lane-row scale (already max/lo,
    clamped), expanded from the per-leaf segment maxima."""
    m, w = u.shape
    rows = w // LANES
    br = _ef21_block(block, rows)
    assert_vmem_budget(4, br)
    lo = float(2 ** (bits - 1) - 1)
    ut, _, rows_p = _tile(u, br)
    ht, _, _ = _tile(u_hat, br)
    with jax.named_scope(RELAYOUT):
        st = row_scales
        if rows_p != rows:
            st = jnp.pad(st, ((0, 0), (0, rows_p - rows)), constant_values=1.0)
        st = st[:, None, :]
    out = pl.pallas_call(
        functools.partial(_qdq_kernel, lo=lo),
        name="ef21_apply",
        grid=(m, rows_p // br),
        in_specs=[
            pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0)),
            _ROW_SCALE_BS(br),
        ],
        out_specs=pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows_p, LANES), u.dtype),
        interpret=interpret,
    )(ut, ht, st)
    return _untile(out, w, (m,))


# ---------------------------------------------------------------------------
# (c) arena-wide eq. (20) inner step with in-kernel server-row broadcast
# ---------------------------------------------------------------------------

def _update_kernel(x_ref, g_ref, xs_ref, lam_ref, o_ref, *, step: float, rho: float):
    f32 = jnp.float32
    out = eq20(x_ref[0].astype(f32), g_ref[0].astype(f32),
               xs_ref[...].astype(f32), lam_ref[0].astype(f32), step, rho)
    o_ref[0] = out.astype(o_ref.dtype)


def _update_kernel_nolam(x_ref, g_ref, xs_ref, o_ref, *, step: float, rho: float):
    # lam-free variant (SCAFFOLD/FedAvg, rho = 0 plain SGD steps): one fewer
    # full (m, width) HBM read per inner step
    f32 = jnp.float32
    out = eq20(x_ref[0].astype(f32), g_ref[0].astype(f32),
               xs_ref[...].astype(f32), None, step, rho)
    o_ref[0] = out.astype(o_ref.dtype)


def _update_kernel_vstep(x_ref, g_ref, xs_ref, lam_ref, step_ref, o_ref, *, rho: float):
    # per-client stepsize read as one value per client row of the block
    # (core.autotune)
    f32 = jnp.float32
    out = eq20(x_ref[0].astype(f32), g_ref[0].astype(f32),
               xs_ref[...].astype(f32), lam_ref[0].astype(f32),
               step_ref[0][:, :1], rho)
    o_ref[0] = out.astype(o_ref.dtype)


def _update_kernel_nolam_vstep(x_ref, g_ref, xs_ref, step_ref, o_ref, *, rho: float):
    f32 = jnp.float32
    out = eq20(x_ref[0].astype(f32), g_ref[0].astype(f32),
               xs_ref[...].astype(f32), None, step_ref[0][:, :1], rho)
    o_ref[0] = out.astype(o_ref.dtype)


def fused_update_arena_pallas(x, g, x_s, lam, step, rho, *, block=None, interpret: bool = False):
    """x, g: (m, width); x_s: (width,) server row (broadcast in-kernel);
    lam: (m, width) or None (dual term dropped).  ``step``: scalar (baked as
    a compile-time constant -- the pre-auto-eta path, bitwise unchanged) or
    (m,) per-client stepsizes riding a broadcast row operand
    (core.autotune).  One pallas_call over the whole packed buffer, written
    into x's buffer where the caller no longer needs x."""
    m, w = x.shape
    lay = _Blocks("fused_update_arena", m, w, x.dtype, 4 if lam is None else 5, block)
    args = [lay.clients(x), lay.clients(g), lay.servers(x_s)]
    in_specs = [lay.client, lay.client, lay.server]
    if lam is not None:
        args.append(lay.clients(lam))
        in_specs.append(lay.client)
    if jnp.ndim(step) > 0:
        assert step.shape == (m,), step.shape
        args.append(lay.rows(step))
        in_specs.append(lay.row)
        kernel = functools.partial(
            _update_kernel_nolam_vstep if lam is None else _update_kernel_vstep,
            rho=float(rho))
    else:
        kernel = functools.partial(
            _update_kernel_nolam if lam is None else _update_kernel,
            step=float(step), rho=float(rho))
    out = pl.pallas_call(
        kernel,
        name="fused_update_arena",
        grid=lay.grid,
        in_specs=in_specs,
        out_specs=lay.client,
        out_shape=lay.out_shape(x.dtype),
        # the step overwrites x, which the inner loop's carry drops: without
        # the alias XLA copies the whole arena before every call
        input_output_aliases={0: 0},
        interpret=interpret,
    )(*args)
    return lay.back(out)


def _update_sum_kernel(x_ref, g_ref, xs_ref, lam_ref, *refs, step, rho: float,
                       carry: bool):
    # refs: ([per-client steps], [running sum], x', sum').  Eq. (20), and
    # beside it the running sum of the iterates: added in f32 and rounded
    # to the state dtype each step, as an XLA add of the two would be
    *ins, o_ref, s_ref = refs
    f32 = jnp.float32
    st = step if step is not None else ins[0][0][:, :1]
    new = eq20(x_ref[0].astype(f32), g_ref[0].astype(f32),
               xs_ref[...].astype(f32), lam_ref[0].astype(f32), st, rho)
    new = new.astype(o_ref.dtype)
    o_ref[0] = new
    acc = new.astype(f32)
    if carry:
        acc = ins[-1][0].astype(f32) + acc
    s_ref[0] = acc.astype(s_ref.dtype)


def fused_update_arena_sum_pallas(x, g, x_s, lam, xsum, step, rho, *, block=None,
                                  interpret: bool = False):
    """Eq. (20) as ``fused_update_arena_pallas`` with lam, and the running
    sum of the inner iterates as a second output: returns (x', sum').
    ``xsum=None`` is the first step: the sum is x' itself, and the call
    reads what the plain kernel reads (x, g, lam, the server row) under its
    name, ``fused_update_arena``.  Later steps read the sum too, under
    ``fused_update_arena_sum``, and write it in place beside x (donate both
    or XLA copies them)."""
    m, w = x.shape
    first = xsum is None
    name = "fused_update_arena" if first else "fused_update_arena_sum"
    lay = _Blocks(name, m, w, x.dtype, 6 if first else 7, block)
    args = [lay.clients(x), lay.clients(g), lay.servers(x_s), lay.clients(lam)]
    in_specs = [lay.client, lay.client, lay.server, lay.client]
    if jnp.ndim(step) > 0:
        assert step.shape == (m,), step.shape
        args.append(lay.rows(step))
        in_specs.append(lay.row)
        step = None
    else:
        step = float(step)
    aliases = {0: 0}
    if not first:
        aliases[len(args)] = 1
        args.append(lay.clients(xsum))
        in_specs.append(lay.client)
    out_sds = lay.out_shape(x.dtype)
    out, acc = pl.pallas_call(
        functools.partial(_update_sum_kernel, step=step, rho=float(rho),
                          carry=not first),
        name=name,
        grid=lay.grid,
        in_specs=in_specs,
        out_specs=(lay.client, lay.client),
        out_shape=(out_sds, out_sds),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*args)
    return lay.back(out), lay.back(acc)


def _update_client_kernel(i_ref, x_ref, g_ref, xs_ref, lam_ref, *refs, bm: int,
                          step, rho: float):
    # refs: (out,), or (per-client steps, out) with one value per client row
    # of the block (core.autotune)
    *step_ref, o_ref = refs
    f32 = jnp.float32
    x = x_ref[0].astype(f32)
    out = eq20(x, g_ref[0].astype(f32), xs_ref[...].astype(f32),
               lam_ref[0].astype(f32), step_ref[0][0][:, :1] if step_ref else step, rho)
    # the block's client rows start at the block of rows that holds client i
    row = i_ref[0] // bm * bm + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    o_ref[0] = jnp.where(row == i_ref[0], out, x).astype(o_ref.dtype)


def fused_update_client_pallas(x, g, x_s, lam, i, step, rho, *, interpret: bool = False):
    """Eq. (20) on client ``i``'s row alone: x, lam (m, width); g (width,)
    client i's gradient; x_s (width,) server row; i a traced int32.
    ``step``: scalar (baked) or (m,) per-client stepsizes riding a row
    operand.  Writes x in place (donate it or XLA copies it); the other
    rows pass through.  The client index rides a scalar-prefetch operand,
    and each grid step reads the ``(1, bm, bw)`` block of rows that holds
    client i: all m rows below a sublane tile, else the tile.  So at m of
    a sublane tile or more a call moves a whole tile of x and lam for one
    client's row."""
    m, w = x.shape
    bm, bw = _flat_blocks(m, w, x.dtype, 5, _sublanes(x.dtype))
    LAYOUT["fused_update_client"] = (bm, bw)
    client = pl.BlockSpec((1, bm, bw), lambda j, i: (0, i[0] // bm, j))
    in_specs = [client, pl.BlockSpec((1, 1, bw), lambda j, i: (0, 0, j)),
                pl.BlockSpec((1, bw), lambda j, i: (0, j)), client]
    args = [x[None], g[None, None], x_s[None], lam[None]]
    if jnp.ndim(step) > 0:
        assert step.shape == (m,), step.shape
        in_specs.append(pl.BlockSpec((1, bm, LANES), lambda j, i: (0, i[0] // bm, 0)))
        with jax.named_scope(RELAYOUT):
            args.append(jnp.broadcast_to(step.astype(jnp.float32)[None, :, None],
                                         (1, m, LANES)))
        step = None
    else:
        step = float(step)
    out = pl.pallas_call(
        functools.partial(_update_client_kernel, bm=bm, step=step, rho=float(rho)),
        name="fused_update_client",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(w, bw),),
            in_specs=in_specs,
            out_specs=client,
        ),
        out_shape=jax.ShapeDtypeStruct((1, m, w), x.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(jnp.reshape(i, (1,)).astype(jnp.int32), *args)
    return out[0]
