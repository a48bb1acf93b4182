"""Pallas TPU kernel: the WHOLE K-step eq. (20) inner loop for affine
gradient oracles, one client per grid step.

For the quadratic testbed (least squares / ridge) the per-client gradient is
affine in arena coordinates:

    grad_i(x) = H_i x - c_i        (H_i = A_i^T A_i + reg I, c_i = A_i^T b_i)

so the K inexact-PDMM steps

    x <- x - step * ((H x - c) + rho * (x - x_s) + lam)        (eq. 20)

form a closed recurrence over VMEM-resident data: the kernel loads one
client's row block (x0, c, lam, the shared server row x_s) and its H matrix
once, runs all K steps with a ``fori_loop`` carrying (x, sum_k x), and writes
x_K and x_bar back.  That is ONE HBM read + ONE write of the client state for
the whole inner loop, versus K round trips for the step-at-a-time path (and
the matvec hits the MXU instead of re-streaming the state through the VPU K
times).

Optional operands (both VMEM-resident per client, loaded once for all K
steps):

  * ``off`` -- a per-client offset row ADDED to the affine constant:
    grad_i(x) = H_i x - (c_i + off_i).  This is the SCAFFOLD control-variate
    hook: the client correction ``- c_i`` rides as ``off = c_i`` (sign folded
    by the caller into c, see ``docs/inner_loop.md``) with ZERO extra HBM
    materialisation -- the arena-resident control-variate buffer is read
    directly.
  * ``lam=None`` drops the dual operand entirely (SCAFFOLD/FedAvg run with
    rho = 0 and no dual): one fewer row-sized HBM read per client.

VMEM budget (``vmem_bytes``): the f32 working set of one grid step is the
(W, W) H block plus ~10 row-sized (W,) buffers (x0/c/xs/lam/off in, x_K/x_bar
out, 2 loop-carry rows), which must fit the shared ``VMEM_CAP_BYTES`` (8 MiB
= half the ~16 MiB/core, leaving room for Pallas' double-buffered pipeline).
That caps W at ~1400 lanes; ``fits_vmem`` is the static gate the round uses
to fall back to the step-at-a-time scan for wider problems.

Layout contract (``core.arena``): W % 128 == 0; H rows/cols and c/off entries
beyond each leaf's true size are ZERO so the padding invariant survives
(padded coordinates see g = 0 - 0 and rho * (0 - 0) + 0, staying 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_update import LANES, RELAYOUT, VMEM_CAP_BYTES, eq20
from repro.kernels.round_tail import client_row


def vmem_bytes(width: int) -> int:
    """f32 working set of one client grid step: H (W x W) + ~10 rows."""
    return 4 * (width * width + 10 * width)


def fits_vmem(width: int) -> bool:
    """Static gate: can the fused K-step kernel hold one client in VMEM?"""
    return width % LANES == 0 and vmem_bytes(width) <= VMEM_CAP_BYTES


def _kernel(*refs, K: int, step, rho: float, has_lam: bool, has_off: bool,
            has_step: bool = False):
    it = iter(refs)
    x_ref, h_ref, c_ref, xs_ref = next(it), next(it), next(it), next(it)
    lam_ref = next(it) if has_lam else None
    off_ref = next(it) if has_off else None
    step_ref = next(it) if has_step else None
    xk_ref, xb_ref = next(it), next(it)

    f32 = jnp.float32
    # every row operand is a (1, 1, W) block of an (m, 1, W) array: [0]
    # gives the client's (1, W) row
    H = h_ref[0].astype(f32)  # (W, W), resident for all K steps
    c = c_ref[0].astype(f32)  # (1, W)
    if off_ref is not None:  # per-client affine offset: g = H x - (c + off)
        c = c + off_ref[0].astype(f32)
    xs = xs_ref[...].astype(f32)
    lam = lam_ref[0].astype(f32) if lam_ref is not None else None
    x0 = x_ref[0].astype(f32)
    if step_ref is not None:  # per-client stepsize operand (core.autotune)
        step = step_ref[0][:, :1]  # (1, 1): the constant row's first lane

    def body(_, carry):
        x, xsum = carry
        # g_j = sum_e H[j, e] x[e]: contract x's lane dim with H's col dim
        g = jax.lax.dot_general(
            x, H, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        ) - c
        x = eq20(x, g, xs, lam, step, rho)
        return x, xsum + x

    x_K, xsum = jax.lax.fori_loop(0, K, body, (x0, jnp.zeros_like(x0)))
    xk_ref[0] = x_K.astype(xk_ref.dtype)
    xb_ref[0] = (xsum * (1.0 / K)).astype(xb_ref.dtype)


def inner_loop_affine_pallas(x0, H, c, x_s, lam, step, rho, K: int, *,
                             off=None, interpret: bool = False):
    """x0, c: (m, W); H: (m, W, W); x_s: (W,) server row (broadcast
    in-kernel); lam: (m, W) or None (dual term dropped); off: (m, W) or None
    (per-client affine offset, g = H x - (c + off)); step: scalar (baked as
    a compile-time constant -- the pre-auto-eta path, bitwise unchanged) or
    (m,) per-client stepsizes loaded as a (1, LANES) row operand per grid
    step (core.autotune).  Returns (x_K, x_bar), both (m, W).

    Client rows travel as (m, 1, W) with (1, 1, W) blocks: a (1, W) block of
    an (m, W) array is not (8, 128)-aligned, which the TPU lowering refuses."""
    m, w = x0.shape
    assert w % LANES == 0, f"arena width {w} not a multiple of {LANES}"
    assert H.shape == (m, w, w) and c.shape == (m, w), (H.shape, c.shape)
    assert lam is None or lam.shape == (m, w), lam.shape
    assert off is None or off.shape == (m, w), off.shape
    assert fits_vmem(w), (
        f"width={w}: fused K-step working set {vmem_bytes(w)} B exceeds the "
        f"{VMEM_CAP_BYTES} B VMEM budget -- use the step-at-a-time path")
    row_bs = pl.BlockSpec((1, 1, w), lambda i: (i, 0, 0))
    out_sds = jax.ShapeDtypeStruct((m, 1, w), x0.dtype)

    def rows3(a):
        with jax.named_scope(RELAYOUT):
            return a.reshape(m, 1, w)

    with jax.named_scope(RELAYOUT):
        xs_row = x_s.reshape(1, w)
    args = [rows3(x0), H, rows3(c), xs_row]
    in_specs = [
        row_bs,
        pl.BlockSpec((1, w, w), lambda i: (i, 0, 0)),
        row_bs,
        pl.BlockSpec((1, w), lambda i: (0, 0)),  # server row: every client
    ]
    if lam is not None:
        args.append(rows3(lam))
        in_specs.append(row_bs)
    if off is not None:
        args.append(rows3(off))
        in_specs.append(row_bs)
    has_step = jnp.ndim(step) > 0
    if has_step:
        assert step.shape == (m,), step.shape
        args.append(client_row(step))
        in_specs.append(pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)))
    x_K, x_bar = pl.pallas_call(
        functools.partial(_kernel, K=int(K),
                          step=None if has_step else float(step),
                          rho=float(rho),
                          has_lam=lam is not None, has_off=off is not None,
                          has_step=has_step),
        name="inner_loop_affine",
        grid=(m,),
        in_specs=in_specs,
        out_specs=(row_bs, row_bs),
        out_shape=(out_sds, out_sds),
        interpret=interpret,
    )(*args)
    with jax.named_scope(RELAYOUT):
        return x_K.reshape(m, w), x_bar.reshape(m, w)
