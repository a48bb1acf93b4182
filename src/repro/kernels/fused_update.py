"""Pallas TPU kernel for the paper's fused federated client step (eq. 20):

    x' = x - step * (g + rho * (x - xs) + lam)

Why a kernel: the GPDMM/AGPDMM inner loop runs K times per round over every
parameter; it is purely memory-bound (arithmetic intensity ~1 flop/byte).  An
unfused XLA graph reads/writes intermediate tensors; the fusion does exactly
4 HBM reads + 1 write per element, the roofline minimum.

Tiling: inputs are flattened and tiled (BLOCK_ROWS, 128) -- the TPU lane width
-- so the kernel is a straight VMEM-resident vector op per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# THE canonical lane width and block default: core.arena packs to LANES
# multiples and every round-tail kernel tiles against it, so both sides
# import these from here -- exactly one knob each.
LANES = 128
BLOCK_ROWS = 256  # 256 x 128 x 4B x 5 arrays ~ 0.7 MB of VMEM per step
# name scope of every reshape, pad and slice between the caller's layout
# and the (rows, LANES) blocks of a Pallas call
RELAYOUT = "relayout"


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m

# VMEM budget for the f32 working set of one grid step: n_arrays x block x
# LANES x 4 B must stay under this.  8 MiB = half the ~16 MiB/core VMEM,
# leaving the other half for Pallas' double-buffered pipeline copies.
VMEM_CAP_BYTES = 8 * 1024 * 1024


def assert_vmem_budget(n_arrays: int, block: int) -> None:
    need = n_arrays * block * LANES * 4
    assert need <= VMEM_CAP_BYTES, (
        f"block={block}: {n_arrays} arrays x {block} rows x {LANES} lanes x 4 B "
        f"= {need} B of VMEM exceeds the {VMEM_CAP_BYTES} B budget "
        f"(max block here: {VMEM_CAP_BYTES // (n_arrays * LANES * 4)})"
    )


def eq20(x, g, xs, lam, step: float, rho: float):
    """The f32 eq. (20) arithmetic, shared by every fused-update kernel body
    (here and in round_tail.py) so the formula has ONE source of truth.
    Inputs are f32 arrays; ``lam=None`` drops the dual term."""
    acc = g + rho * (x - xs)
    if lam is not None:
        acc = acc + lam
    return x - step * acc


def _kernel(x_ref, g_ref, xs_ref, lam_ref, o_ref, *, step: float, rho: float):
    f32 = jnp.float32
    out = eq20(x_ref[...].astype(f32), g_ref[...].astype(f32),
               xs_ref[...].astype(f32), lam_ref[...].astype(f32), step, rho)
    o_ref[...] = out.astype(o_ref.dtype)


def _kernel_nolam(x_ref, g_ref, xs_ref, o_ref, *, step: float, rho: float):
    # lam-free variant (Inexact FedSplit): one fewer HBM read per element
    f32 = jnp.float32
    out = eq20(x_ref[...].astype(f32), g_ref[...].astype(f32),
               xs_ref[...].astype(f32), None, step, rho)
    o_ref[...] = out.astype(o_ref.dtype)


def fused_update_pallas(x, g, xs, lam, step, rho, *, block: int = BLOCK_ROWS, interpret: bool = False):
    args = [x, g, xs] if lam is None else [x, g, xs, lam]
    assert_vmem_budget(len(args) + 1, block)
    shape, dtype = x.shape, x.dtype
    n = x.size
    tile = block * LANES
    n_pad = (tile - n % tile) % tile

    def flat(a):
        a = a.reshape(-1)
        if n_pad:
            a = jnp.pad(a, (0, n_pad))
        return a.reshape(-1, LANES)

    with jax.named_scope(RELAYOUT):
        flats = [flat(a) for a in args]
    rows = flats[0].shape[0]
    grid = (rows // block,)
    bs = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    kernel = _kernel_nolam if lam is None else _kernel
    out = pl.pallas_call(
        functools.partial(kernel, step=float(step), rho=float(rho)),
        name="fused_update",
        grid=grid,
        in_specs=[bs] * len(flats),
        out_specs=bs,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        interpret=interpret,
    )(*flats)
    with jax.named_scope(RELAYOUT):
        return out.reshape(-1)[:n].reshape(shape)
