"""Flat client-state arena + fused round-tail kernels (ISSUE 1 tentpole).

Covers: pack/unpack round trips across dtypes and odd (non-multiple-of-128)
leaf sizes, interpret-mode parity of every round-tail kernel (the
elementwise ones bit for bit against the XLA path, on the tiled and the
flat layout with ragged blocks), arena-vs-pytree parity of whole GPDMM/AGPDMM/
FedSplit rounds (incl. the EF21-quantised and partial-participation
variants), the KKT invariant on the arena path, and the VMEM budget guard.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.configs.base import FederatedConfig
from repro.core import arena, fedsplit, make, quadratic
from repro.core import tree_util as T
from repro.kernels import ops
from repro.kernels.fused_update import BLOCK_ROWS, fused_update_pallas

IMPLS = ["xla", "pallas_interpret"]

# odd, non-multiple-of-128 leaf sizes on purpose (incl. a scalar)
ODD_TREE_SHAPES = {"a": (7,), "b": {"w": (3, 50), "s": ()}, "c": (130,)}


def odd_tree(key, dtype=jnp.float32, m=None):
    leaves = {}
    ks = iter(jax.random.split(key, 8))

    def mk(shape):
        lead = () if m is None else (m,)
        return jax.random.normal(next(ks), lead + shape).astype(dtype)

    leaves = {"a": mk((7,)), "b": {"w": mk((3, 50)), "s": mk(())}, "c": mk((130,))}
    return leaves


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_unpack_roundtrip(dtype):
    tree = odd_tree(jax.random.key(0), dtype)
    spec = arena.ArenaSpec.from_tree(tree)
    row = spec.pack(tree)
    assert row.shape == (spec.width,) and spec.width % arena.LANES == 0
    back = spec.unpack(row)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_unpack_stacked_roundtrip(dtype):
    m = 5
    tree = odd_tree(jax.random.key(1), dtype, m=m)
    spec = arena.ArenaSpec.from_tree(tree, stacked=True)
    buf = spec.pack_stacked(tree)
    assert buf.shape == (m, spec.width)
    back = spec.unpack_stacked(buf)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))


def test_slice_table_lane_aligned():
    spec = arena.ArenaSpec.from_tree(odd_tree(jax.random.key(2)))
    off = 0
    for e in spec.leaves:
        assert e.offset == off and e.offset % arena.LANES == 0
        assert e.padded % arena.LANES == 0 and e.padded >= e.size
        off += e.padded
    assert spec.width == off
    assert sum(spec.leaf_rows()) == spec.n_rows


def test_padding_stays_zero():
    tree = odd_tree(jax.random.key(3), m=4)
    spec = arena.ArenaSpec.from_tree(tree, stacked=True)
    buf = spec.pack_stacked(tree)
    mask = np.ones((spec.width,), bool)
    for e in spec.leaves:
        mask[e.offset:e.offset + e.size] = False
    assert np.all(np.asarray(buf)[:, mask] == 0.0)


def test_leaf_view_matches_leaf():
    tree = odd_tree(jax.random.key(4), m=3)
    spec = arena.ArenaSpec.from_tree(tree, stacked=True)
    buf = spec.pack_stacked(tree)
    leaves = jax.tree.leaves(tree)
    for i in range(len(spec.leaves)):
        np.testing.assert_array_equal(np.asarray(spec.leaf_view(buf, i)), np.asarray(leaves[i]))


# ---------------------------------------------------------------------------
# kernel parity (interpret mode) vs plain float32 arithmetic and the XLA path
# ---------------------------------------------------------------------------

# (m, width) of the elementwise arena kernels' parity cases.  "odd": the
# packed odd-size tree, m = 5 below every sublane tile (one block of all
# rows); "ragged": m = 37 clients in blocks of 32 rows, and the width of 131
# lane rows in three 5632-lane blocks, the last of each ragged
ARENA_CASES = {"odd": (5, None), "ragged": (37, 131 * 128)}


def arena_operands(case, dtype, n_clients, n_rows, seed):
    """``n_clients`` (m, width) client buffers, ``n_rows`` (width,) server rows
    and an (m,) per-client stepsize of ``case``."""
    m, w = ARENA_CASES[case]
    if w is None:
        w = arena.ArenaSpec.from_tree(odd_tree(jax.random.key(0))).width
    ks = iter(jax.random.split(jax.random.key(seed), n_clients + n_rows + 1))
    clients = [jax.random.normal(next(ks), (m, w)).astype(dtype) for _ in range(n_clients)]
    rows = [jax.random.normal(next(ks), (w,)).astype(dtype) for _ in range(n_rows)]
    step = jax.random.uniform(next(ks), (m,), minval=0.01, maxval=0.1)
    return clients, rows, step


def f32(a):
    return np.asarray(a, np.float32)


def assert_layout(op, impl, m, dtype):
    """The Pallas call read the arena in blocks of whole sublane tiles of
    clients (8 rows of 32-bit values, 16 of 16-bit), or in one block of all
    m rows where m is below a tile."""
    if impl == "pallas_interpret":
        sub = 32 // jnp.dtype(dtype).itemsize
        bm = ops.LAYOUT[op][0]
        assert bm == m if m < sub else bm % sub == 0, ops.LAYOUT[op]


def assert_bitwise_xla(fn, *args):
    """``fn(impl, *args)`` gives the same bits with the Pallas kernel
    (interpret mode) as with ``impl="xla"``, both jitted: the blocks,
    ragged edges included, change no element.  (Eagerly, the XLA path runs
    op by op and rounds where the jitted graph may fuse a multiply-add.)"""
    got, want = (jax.jit(functools.partial(fn, impl))(*args)
                 for impl in ("pallas_interpret", "xla"))
    np.testing.assert_array_equal(f32(got), f32(want))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_round_tail_parity(impl, case, dtype):
    rho = 2.5
    (x, lam), (xs,), _ = arena_operands(case, dtype, 2, 1, 5)
    m = x.shape[0]
    xf, lf, sf = f32(x), f32(lam), f32(xs)[None]
    lam_is_exp = rho * (sf - xf) - lf
    up_exp = xf - lam_is_exp / rho

    lam_is, up = ops.round_tail(x, lam, xs, rho, impl=impl)
    assert_layout("round_tail", impl, m, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(f32(lam_is), lam_is_exp, atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(up), up_exp, atol=tol, rtol=tol)

    lam_new = ops.dual_from_uplink(up, xs, rho, impl=impl)
    assert_layout("dual_from_uplink", impl, m, dtype)
    exp = rho * (f32(up) - sf)
    np.testing.assert_allclose(f32(lam_new), exp, atol=tol, rtol=tol)

    # uplink-only hot-path variant: same uplink, no lam_is output
    none_lam, up2 = ops.round_tail(x, lam, xs, rho, with_lam_is=False, impl=impl)
    assert none_lam is None
    np.testing.assert_allclose(f32(up2), f32(up), atol=tol, rtol=tol)
    if impl == "pallas_interpret":
        assert_bitwise_xla(lambda i, x, lam, xs: ops.round_tail(
            x, lam, xs, rho, impl=i), x, lam, xs)
        assert_bitwise_xla(lambda i, x, lam, xs: ops.round_tail(
            x, lam, xs, rho, with_lam_is=False, impl=i)[1], x, lam, xs)
        assert_bitwise_xla(lambda i, u, xs: ops.dual_from_uplink(
            u, xs, rho, impl=i), up, xs)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ef21_parity(impl, bits, dtype):
    """Fused EF21 == per-leaf tree_quantize_delta, incl. the per-(client,
    leaf) max-abs quantisation scale granularity."""
    m = 6
    u_tree = odd_tree(jax.random.key(8), dtype, m=m)
    uh_tree = jax.tree.map(lambda t: t * 0.7, u_tree)
    spec = arena.ArenaSpec.from_tree(u_tree, stacked=True)
    ref = spec.pack_stacked(T.tree_quantize_delta(u_tree, uh_tree, bits))
    got = ops.ef21_update(
        spec.pack_stacked(u_tree), spec.pack_stacked(uh_tree), bits, spec.leaf_rows(), impl=impl
    )
    tol = 1e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_client_step", [False, True])
@pytest.mark.parametrize("with_lam", [True, False])
def test_fused_update_arena_parity(impl, case, dtype, per_client_step, with_lam):
    """Eq. (20) over the arena in all four kernel variants: scalar or
    per-client step, with the dual term or without (lam=None)."""
    (x, g, lam), (xs,), step_v = arena_operands(case, dtype, 3, 1, 9)
    m = x.shape[0]
    step = step_v if per_client_step else 0.05
    lam = lam if with_lam else None
    out = ops.fused_update_arena(x, g, xs, lam, step, 3.0, impl=impl)
    assert_layout("fused_update_arena", impl, m, dtype)
    step_e = np.asarray(step_v)[:, None] if per_client_step else 0.05
    acc = f32(g) + 3.0 * (f32(x) - f32(xs)[None])
    if with_lam:
        acc = acc + f32(lam)
    exp = f32(x) - step_e * acc
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(f32(out), exp, atol=tol, rtol=tol)
    if impl == "pallas_interpret":
        assert_bitwise_xla(lambda i, x, g, xs, lam, step: ops.fused_update_arena(
            x, g, xs, lam, step if per_client_step else 0.05, 3.0, impl=i),
            x, g, xs, lam, step_v)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_client_step", [False, True])
@pytest.mark.parametrize("form", ["first", "accumulate"])
def test_fused_update_arena_sum_parity(impl, case, dtype, per_client_step, form):
    """Eq. (20) with the running sum of the inner iterates beside it: x' is
    bitwise the plain kernel's, and the sum bitwise the XLA add of x' in
    the state dtype (the first step's sum is x' itself), in both dtypes."""
    (x, g, lam, xsum), (xs,), step_v = arena_operands(case, dtype, 4, 1, 19)
    m = x.shape[0]
    xsum = xsum if form == "accumulate" else None
    step = step_v if per_client_step else 0.05
    out, acc = ops.fused_update_arena_sum(x, g, xs, lam, xsum, step, 3.0, impl=impl)
    name = "fused_update_arena" if xsum is None else "fused_update_arena_sum"
    assert ops.RESOLVED[name] == impl
    assert_layout(name, impl, m, dtype)
    want = ops.fused_update_arena(x, g, xs, lam, step, 3.0, impl=impl)
    np.testing.assert_array_equal(f32(out), f32(want))
    np.testing.assert_array_equal(
        f32(acc), f32(want if xsum is None else xsum + want))
    assert acc.dtype == dtype
    if impl == "pallas_interpret":
        assert_bitwise_xla(lambda i, x, g, xs, lam, st: ops.fused_update_arena_sum(
            x, g, xs, lam, xsum, st if per_client_step else 0.05, 3.0, impl=i),
            x, g, xs, lam, step_v)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_lam_is", [False, True])
def test_round_tail_scale_parity(impl, case, dtype, with_lam_is):
    """The uplink kernel handed the running sum of K = 3 iterates and the
    scale 1/K gives bitwise what it gives on the average formed by an XLA
    ``sum * (1/K)`` first, in both dtypes.  One allowance: on the CPU, XLA
    may contract the interpret-mode kernel's f32 ``sum * scale`` into the
    subtract that reads it (one FMA: x_bar unrounded), so there the f32
    outputs are held to four ulps of their largest element instead."""
    rho, K = 2.5, 3
    (xsum, lam), (xs,), _ = arena_operands(case, dtype, 2, 1, 23)
    xsum = (xsum * K).astype(dtype)
    got = jax.jit(lambda a, b, c: ops.round_tail(
        a, b, c, rho, with_lam_is=with_lam_is, scale=1.0 / K, impl=impl))(xsum, lam, xs)
    want = jax.jit(lambda a, b, c: ops.round_tail(
        a * (1.0 / K), b, c, rho, with_lam_is=with_lam_is, impl=impl))(xsum, lam, xs)
    assert (got[0] is None) == (not with_lam_is)
    for a, b in zip(got, want):
        if a is None:
            continue
        assert a.dtype == dtype
        if impl == "pallas_interpret" and dtype == jnp.float32:
            ulp = np.spacing(np.abs(f32(b)).max())
            np.testing.assert_allclose(f32(a), f32(b), rtol=0, atol=4 * ulp)
        else:
            np.testing.assert_array_equal(f32(a), f32(b))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_client_parity(impl, case, dtype):
    """Eq. (20) on one client's row of the arena: that row stepped, every
    other row bitwise as it was, for the first, a middle and the last
    client (blocks of all m rows below a sublane tile, else of the tile
    that holds the client)."""
    (x, lam), (g, xs), _ = arena_operands(case, dtype, 2, 2, 11)
    m = x.shape[0]
    for i in sorted({0, m // 2, m - 1}):
        out = ops.fused_update_client(x, g, xs, lam, jnp.int32(i), 0.05, 3.0, impl=impl)
        exp = f32(x[i]) - 0.05 * (f32(g) + 3.0 * (f32(x[i]) - f32(xs)) + f32(lam[i]))
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(f32(out[i]), exp, atol=tol, rtol=tol)
        rest = np.arange(m) != i
        np.testing.assert_array_equal(f32(out)[rest], f32(x)[rest])
        if impl == "pallas_interpret":
            assert_bitwise_xla(lambda im, x, g, xs, lam: ops.fused_update_client(
                x, g, xs, lam, jnp.int32(i), 0.05, 3.0, impl=im), x, g, xs, lam)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
def test_fused_update_client_per_client_step(impl, case):
    """As above with (m,) per-client stepsizes (``core.autotune``): row i
    takes its own step, every other row stays bitwise as it was."""
    (x, lam), (g, xs), step_v = arena_operands(case, jnp.float32, 2, 2, 17)
    m = x.shape[0]
    for i in sorted({0, m // 2, m - 1}):
        out = ops.fused_update_client(x, g, xs, lam, jnp.int32(i), step_v, 3.0, impl=impl)
        exp = f32(x[i]) - f32(step_v[i]) * (
            f32(g) + 3.0 * (f32(x[i]) - f32(xs)) + f32(lam[i]))
        np.testing.assert_allclose(f32(out[i]), exp, atol=1e-5, rtol=1e-5)
        rest = np.arange(m) != i
        np.testing.assert_array_equal(f32(out)[rest], f32(x)[rest])
        if impl == "pallas_interpret":
            assert_bitwise_xla(lambda im, x, g, xs, lam, st: ops.fused_update_client(
                x, g, xs, lam, jnp.int32(i), st, 3.0, impl=im), x, g, xs, lam, step_v)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
@pytest.mark.parametrize("per_client_alpha", [False, True])
def test_scaffold_cv_parity(impl, case, per_client_alpha):
    """SCAFFOLD's control-variate refresh c_i' = c_i - c + alpha (x_s - x_K)
    with two server rows, for a scalar and a per-client alpha."""
    (c_i, x_k), (c_s, xs), step_v = arena_operands(case, jnp.float32, 2, 2, 13)
    alpha_v = 1.0 / (5 * step_v)
    alpha = alpha_v if per_client_alpha else 4.0
    out = ops.scaffold_cv(c_i, x_k, c_s, xs, alpha, impl=impl)
    assert_layout("scaffold_cv", impl, c_i.shape[0], jnp.float32)
    alpha_e = np.asarray(alpha_v)[:, None] if per_client_alpha else 4.0
    exp = f32(c_i) - f32(c_s)[None] + alpha_e * (f32(xs)[None] - f32(x_k))
    np.testing.assert_allclose(f32(out), exp, atol=1e-5, rtol=1e-5)
    if impl == "pallas_interpret":
        assert_bitwise_xla(lambda i, c, x, cs, xs, a: ops.scaffold_cv(
            c, x, cs, xs, a if per_client_alpha else 4.0, impl=i),
            c_i, x_k, c_s, xs, alpha_v)


@pytest.mark.parametrize("impl", IMPLS)
def test_fused_update_nolam(impl):
    """lam=None drops the dual term (FedSplit's lam-free step): one fewer
    HBM read, same math as lam=0."""
    k = jax.random.key(11)
    x, g, xs = (jax.random.normal(jax.random.fold_in(k, i), (5, 300)) for i in range(3))
    out = ops.fused_update(x, g, xs, None, 0.05, 3.0, impl=impl)
    exp = np.asarray(x) - 0.05 * (np.asarray(g) + 3.0 * (np.asarray(x) - np.asarray(xs)))
    np.testing.assert_allclose(np.asarray(out), exp, atol=1e-5, rtol=1e-5)


def test_vmem_budget_guard():
    """block sizes whose f32 working set exceeds the documented cap are
    rejected; the unified default passes."""
    x = jnp.ones((256,))
    with pytest.raises(AssertionError, match="VMEM"):
        fused_update_pallas(x, x, x, x, 0.1, 1.0, block=100_000, interpret=True)
    out = fused_update_pallas(x, x, x, x, 0.1, 1.0, block=BLOCK_ROWS, interpret=True)
    assert out.shape == x.shape


# ---------------------------------------------------------------------------
# whole-round parity: arena path == pytree path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prob():
    return quadratic.generate(jax.random.key(0), m=8, n=120, d=24)


VARIANTS = {
    "plain": {},
    "ef21": {"uplink_bits": 8},
    "partial": {"participation": 0.5},
    "ef21+partial": {"uplink_bits": 8, "participation": 0.5},
    "last_iter": {"use_avg": False},
}


@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_round_parity_arena_vs_pytree(prob, algo, variant):
    """GPDMM/AGPDMM rounds on the arena path are bitwise-comparable (within
    dtype tolerance) to the pytree path -- the ISSUE's acceptance criterion."""
    kw = dict(algorithm=algo, inner_steps=3, eta=0.5 / prob.L, **VARIANTS[variant])
    x0 = jnp.zeros((prob.d,))
    batch = prob.batch()
    states = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(use_arena=use_arena, **kw))
        s = opt.init(x0, prob.m)
        for _ in range(5):
            s, metrics = opt.round(s, prob.grad, batch)
        states[use_arena] = (s, metrics)
    sa, ma = states[True]
    sp, mp = states[False]
    assert set(sa) == set(sp)
    spec = arena.ArenaSpec.from_tree(sp["x_s"])
    for ka in sorted(sa):
        got, want = sa[ka], sp[ka]
        if ka != "x_s" and ka != "round":
            want = spec.pack_stacked(want)  # arena path keeps clients packed
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(got)[0]), np.asarray(jax.tree.leaves(want)[0]),
            atol=1e-5, rtol=1e-5, err_msg=f"{algo}/{variant}: state[{ka}]")
    for km in ma:
        if km == "used_arena":  # records the layout decision: differs by design
            continue
        np.testing.assert_allclose(float(ma[km]), float(mp[km]), atol=1e-4,
                                   err_msg=f"{algo}/{variant}: metrics[{km}]")


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
def test_round_parity_by_client(prob, algo, impl, monkeypatch):
    """A plain gradient oracle (no arena attributes) steps the arena a
    client at a time (``core.api.step_by_client``): the rounds match the
    pytree path's, with the kernels in XLA and in Pallas."""
    monkeypatch.setattr(ops, "default_impl", lambda: impl)
    kw = dict(algorithm=algo, inner_steps=3, eta=0.5 / prob.L)
    x0 = jnp.zeros((prob.d,))
    batch = prob.batch()
    states = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(use_arena=use_arena, **kw))
        s = opt.init(x0, prob.m)
        for _ in range(3):
            s, _ = opt.round(s, lambda x, b: prob.grad(x, b), batch)
        states[use_arena] = s
    sa, sp = states[True], states[False]
    assert ops.RESOLVED["fused_update_client"] == impl
    assert set(sa) == set(sp)
    spec = arena.ArenaSpec.from_tree(sp["x_s"])
    # the duals rho (u - x_s) carry the float32 rounding of the states (a
    # few 1e-7 at their size, about 2) times rho (165 here)
    rho = 1.0 / (kw["inner_steps"] * kw["eta"])
    for k in sorted(set(sa) - {"round"}):
        want = sp[k] if k == "x_s" else spec.pack_stacked(sp[k])
        atol = 1e-6 * rho if k.startswith("lam") else 1e-5
        np.testing.assert_allclose(np.asarray(sa[k]), np.asarray(want),
                                   atol=atol, rtol=1e-5, err_msg=k)


SOFTMAX_F, SOFTMAX_C = 20, 5


def softmax_problem(m, K, seed=21):
    """The paper's softmax regression at a small size: its arena-native
    oracle, per-step mini-batches (K, m, 8, F) and a start point."""
    from repro.core.softmax import SoftmaxRegression

    sm = SoftmaxRegression(SOFTMAX_F, SOFTMAX_C)
    kx, ky, kw = jax.random.split(jax.random.key(seed), 3)
    batch = {"x": jax.random.normal(kx, (K, m, 8, SOFTMAX_F)),
             "y": jax.random.randint(ky, (K, m, 8), 0, SOFTMAX_C)}
    return sm.oracle(), batch, 0.1 * jax.random.normal(kw, (sm.dim,))


def xla_average(monkeypatch):
    """Route the round through the composition the summing kernels replace:
    each eq. (20) step by the plain kernel, ``xsum + x_new`` as an XLA add
    after it from a zeros buffer, and ``xsum * (1/K)`` as an XLA multiply
    ahead of the unscaled uplink kernel."""
    plain, tail = ops.fused_update_arena, ops.round_tail

    def summed(x, g, x_s, lam, xsum, step, rho):
        new = plain(x, g, x_s, lam, step, rho)
        return new, (jnp.zeros_like(x) if xsum is None else xsum) + new

    def scaled(x_ref, lam, x_s, rho, *, scale=None, **kw):
        return tail(x_ref if scale is None else x_ref * scale, lam, x_s, rho, **kw)

    monkeypatch.setattr(ops, "fused_update_arena_sum", summed)
    monkeypatch.setattr(ops, "round_tail", scaled)


@pytest.mark.parametrize("eta", ["scalar", "per_client"])
@pytest.mark.parametrize("body", ["masked", "cohort", "popstore"])
def test_round_average_formed_in_kernels(body, eta, monkeypatch):
    """A GPDMM arena round on softmax regression whose uplink reads the
    average of the inner iterates (eq. 23) forms that average inside the
    kernels -- the eq. (20) kernel carries the running sum, the uplink
    kernel applies 1/K -- and its state comes out bitwise equal, at f32, to
    the composition of XLA passes it replaces, with the Pallas kernels in
    interpret mode: the full masked round, the cohort round and the
    popstore body, at a scalar and a per-client stepsize."""
    from repro.core import gpdmm

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas_interpret")
    m, K = 6, 3
    oracle, batch, x0 = softmax_problem(m, K)
    eta_v = 0.5 if eta == "scalar" else tuple(np.linspace(0.3, 0.7, m).tolist())
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=K, eta=eta_v,
                          use_arena=True, cohort=body != "masked",
                          participation=1.0 if body == "masked" else 0.5)
    spec = arena.ArenaSpec.from_tree(x0)

    def run():
        if body == "popstore":
            idx = T.cohort_indices(gpdmm.participation_key(cfg, 0), m,
                                   cfg.participation)[0]
            row = spec.pack(x0)
            k1, k2 = jax.random.split(jax.random.key(3))
            staged = {"u_hat": row + 0.01 * jax.random.normal(k1, (idx.shape[0], spec.width)),
                      "x_c": row + 0.01 * jax.random.normal(k2, (idx.shape[0], spec.width))}
            body_fn = gpdmm.popstore_body(cfg, spec, m, oracle, True)
            return jax.jit(body_fn)({"x_s": x0}, staged, idx, jnp.int32(0), batch)[0]
        opt = make(cfg)
        step = jax.jit(lambda s: opt.round(s, oracle, batch, True))
        s = opt.init(x0, m)
        for _ in range(2):  # the second round steps from nonzero duals
            s, _ = step(s)
        return s

    monkeypatch.setattr(ops, "RESOLVED", {})
    got = run()
    assert ops.RESOLVED["fused_update_arena_sum"] == "pallas_interpret"
    xla_average(monkeypatch)
    monkeypatch.setattr(ops, "RESOLVED", {})
    want = run()
    assert "fused_update_arena_sum" not in ops.RESOLVED
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("algo, extra, summing", [
    ("gpdmm", {}, True),
    ("gpdmm", {"use_avg": False}, False),
    ("agpdmm", {}, False),
    ("scaffold", {}, False),
    ("fedavg", {}, False),
    ("fedsplit", {"rho": 1.0}, False),
])
def test_running_sum_kernel_only_where_the_average_is_read(algo, extra, summing,
                                                           monkeypatch):
    """The summing eq. (20) kernel runs only in GPDMM rounds whose uplink
    reads the average of the iterates: once per step after the first, which
    keeps the plain kernel's name.  AGPDMM, ``use_avg=False``, SCAFFOLD,
    FedAvg and FedSplit trace the kernels they traced before, and the
    average's running sum is not formed at all."""
    import re

    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
    m, K = 4, 3
    oracle, batch, x0 = softmax_problem(m, K)
    opt = make(FederatedConfig(algorithm=algo, inner_steps=K, eta=0.5,
                               use_arena=True, **extra))
    state = opt.init(x0, m)
    jaxpr = str(jax.make_jaxpr(lambda s: opt.round(s, oracle, batch, True))(state))
    names = re.findall(r"\bname=(\w+)", jaxpr)
    assert ("fused_update_arena_sum" in names) == summing, names
    if algo in ("gpdmm", "agpdmm"):
        assert "fused_update_arena" in names and "round_tail" in names, names


@pytest.mark.parametrize("init", ["z", "xs"])
def test_fedsplit_round_parity(prob, init):
    x0 = jnp.zeros((prob.d,))
    batch = prob.batch()
    states = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(algorithm="fedsplit", inner_steps=3, eta=1.0 / prob.L,
                                   fedsplit_init=init, rho=prob.L / 10, use_arena=use_arena))
        s = opt.init(x0, prob.m)
        for _ in range(5):
            s, _ = opt.round(s, prob.grad, batch)
        states[use_arena] = s
    np.testing.assert_allclose(np.asarray(states[True]["x_s"]), np.asarray(states[False]["x_s"]),
                               atol=1e-5, rtol=1e-5)
    spec = arena.ArenaSpec.from_tree(states[False]["x_s"])
    np.testing.assert_allclose(np.asarray(states[True]["z_s"]),
                               np.asarray(spec.pack_stacked(states[False]["z_s"])),
                               atol=1e-5, rtol=1e-5)


def test_trace_parity(prob):
    """return_trace quantities (theory checks) match across paths."""
    x0 = jnp.zeros((prob.d,))
    traces = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=3, eta=0.5 / prob.L,
                                   use_arena=use_arena))
        s = opt.init(x0, prob.m)
        s, metrics = opt.round(s, prob.grad, prob.batch(), return_trace=True)
        traces[use_arena] = metrics["trace"]
    for k in traces[True]:
        np.testing.assert_allclose(np.asarray(traces[True][k]), np.asarray(traces[False][k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)


def test_mixed_dtype_falls_back_to_pytree():
    """Mixed-dtype trees (bf16 weights + f32 norms) take the pytree path:
    a single arena buffer would promote everything to the widest dtype --
    2x the client-state HBM and a numerical divergence."""
    params = {"w": jnp.ones((37, 5), jnp.bfloat16), "b": jnp.zeros((3,), jnp.float32)}

    def grad_fn(p, _b):
        return jax.tree.map(lambda x: (0.3 * x.astype(jnp.float32)).astype(x.dtype), p)

    batch = {"d": jnp.zeros((4, 1))}
    outs = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.1,
                                   use_arena=use_arena))
        s = opt.init(params, 4)
        # both configs must produce the identical (pytree) state layout
        assert jax.tree.leaves(s["lam_s"])[0].shape[1:] != (0,)  # smoke
        for _ in range(2):
            s, _ = opt.round(s, grad_fn, batch)
        outs[use_arena] = s["x_s"]
    for a, b in zip(jax.tree.leaves(outs[True]), jax.tree.leaves(outs[False])):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_svrg_parity():
    """SVRG per-step-batch inner loop matches across paths."""
    key = jax.random.key(5)
    m, d, K = 4, 16, 3
    params = jnp.zeros((d,))
    batch = {"w": jax.random.normal(key, (K, m, d))}

    def grad_fn(x, b):
        return 0.3 * x + 0.01 * b["w"]

    outs = {}
    for use_arena in [True, False]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=K, eta=0.1,
                                   variance_reduction="svrg", use_arena=use_arena))
        s = opt.init(params, m)
        for _ in range(3):
            s, _ = opt.round(s, grad_fn, batch, per_step_batches=True)
        outs[use_arena] = s["x_s"]
    np.testing.assert_allclose(np.asarray(outs[True]), np.asarray(outs[False]), atol=1e-6)


# ---------------------------------------------------------------------------
# KKT invariant (eq. 25) on the arena path, for ANY parameter pytree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
def test_kkt_invariant_arena(prob, algo):
    opt = make(FederatedConfig(algorithm=algo, inner_steps=3, eta=0.5 / prob.L, use_arena=True))
    s = opt.init(jnp.zeros((prob.d,)), prob.m)
    for _ in range(10):
        s, metrics = opt.round(s, prob.grad, prob.batch())
        assert float(metrics["lam_sum_norm"]) < 1e-3


@st.composite
def _pytrees(draw):
    n_leaves = draw(st.integers(1, 3))
    tree = {}
    for i in range(n_leaves):
        shape = tuple(draw(st.lists(st.integers(1, 6), min_size=1, max_size=2)))
        tree[f"w{i}"] = jnp.full(shape, float(i + 1))
    return tree


@settings(max_examples=10, deadline=None)
@given(params=_pytrees(), algo=st.sampled_from(["gpdmm", "agpdmm"]),
       m=st.integers(2, 4), k=st.integers(1, 3))
def test_kkt_invariant_arena_property(params, algo, m, k):
    """sum_i lam_{s|i} == 0 holds on the arena path for arbitrary pytrees."""
    opt = make(FederatedConfig(algorithm=algo, inner_steps=k, eta=0.1, use_arena=True))

    def grad_fn(p, _b):
        return jax.tree.map(lambda x: 0.3 * x, p)

    s = opt.init(params, m)
    s2, metrics = opt.round(s, grad_fn, {"dummy": jnp.zeros((m, 1))})
    assert jax.tree.structure(s2) == jax.tree.structure(s)
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(s2)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert float(metrics["lam_sum_norm"]) < 1e-4
