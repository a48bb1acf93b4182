"""ISSUE 2: arena-native gradient oracles, the fused K-step inner-loop
kernel, and the round-batched scan driver.

Covers: interpret-mode parity of the fused affine K-step kernel against both
``inner_steps`` (pytree) and the step-at-a-time arena scan over K in {1, 4}
and odd (non-multiple-of-128) widths; the SVRG variant through the
arena-native oracle; round-batched-scan vs loop-of-rounds state equality;
the closed-form softmax oracle vs jax.grad; ridge-regularised quadratics;
the participation-seed contract; and the use_arena="auto" width dispatch
(recorded in round metrics).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederatedConfig
from repro.core import arena, make, make_scan_rounds, quadratic
from repro.core import tree_util as T
from repro.core.gpdmm import (
    avg_scale, inner_steps, inner_steps_arena, participation_key,
)
from repro.core.softmax import SoftmaxRegression
from repro.kernels import ops
from repro.kernels.fused_update import VMEM_CAP_BYTES
from repro.kernels.inner_loop import fits_vmem, vmem_bytes

IMPLS = ["xla", "pallas_interpret"]


@pytest.fixture(scope="module", params=[24, 130], ids=["d24", "d130_odd"])
def prob(request):
    # d=24 -> width 128; d=130 -> width 256 with 126 zero-padded columns
    return quadratic.generate(jax.random.key(0), m=6, n=80, d=request.param)


# ---------------------------------------------------------------------------
# fused K-step kernel parity: pallas_interpret == xla == inner_steps(_arena)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("K", [1, 4])
def test_inner_loop_affine_parity(prob, impl, K):
    """The single-kernel K-step loop reproduces the step-at-a-time references
    on both the pytree and arena paths, padding included."""
    m, d = prob.m, prob.d
    eta = 0.5 / prob.L
    rho = 1.0 / (K * eta)
    step_c = 1.0 / (1.0 / eta + rho)
    spec = arena.ArenaSpec.from_tree(jnp.zeros((d,)))
    key = jax.random.key(1)
    x0_t = jax.random.normal(jax.random.fold_in(key, 0), (m, d))
    lam_t = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    xs_t = jax.random.normal(jax.random.fold_in(key, 2), (d,))

    # reference 1: pytree inner loop (vmapped per-client grad)
    x_K_ref, x_bar_ref = inner_steps(
        prob.grad, x0_t, T.tree_broadcast(xs_t, m), lam_t, prob.batch(),
        K=K, eta=eta, rho=rho, per_step=False)

    # reference 2: step-at-a-time arena scan with the plain (wrapped) grad
    x0a, lama = spec.pack_stacked(x0_t), spec.pack_stacked(lam_t)
    xsa = spec.pack(xs_t)
    x_K_scan, x_sum = inner_steps_arena(
        spec, prob.grad, x0a, xsa, lama, prob.batch(),
        K=K, eta=eta, rho=rho, per_step=False)
    x_bar_scan = x_sum * avg_scale(spec, prob.grad, K, per_step=False)

    # the fused kernel under test
    oracle = prob.oracle()
    H, c = oracle.affine_arena(spec, prob.batch())
    x_K, x_bar = ops.inner_loop_affine(x0a, H, c, xsa, lama, step_c, rho, K, impl=impl)

    for got, want, name in [(x_K, x_K_scan, "x_K"), (x_bar, x_bar_scan, "x_bar")]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
        # padding columns stay identically zero (arena invariant)
        assert np.all(np.asarray(got)[:, d:] == 0.0), name
    np.testing.assert_allclose(np.asarray(x_K[:, :d]), np.asarray(x_K_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(x_bar[:, :d]), np.asarray(x_bar_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
@pytest.mark.parametrize("K", [1, 4])
def test_round_parity_with_oracle(prob, algo, K):
    """Whole rounds driven by the annotated oracle (affine fused path on the
    arena) match the plain-grad pytree path."""
    kw = dict(algorithm=algo, inner_steps=K, eta=0.5 / prob.L)
    x0 = jnp.zeros((prob.d,))
    outs = {}
    for use_arena, grad in [(True, prob.oracle()), (False, prob.grad)]:
        opt = make(FederatedConfig(use_arena=use_arena, **kw))
        s = opt.init(x0, prob.m)
        for _ in range(4):
            s, metrics = opt.round(s, grad, prob.batch())
        outs[use_arena] = np.asarray(s["x_s"])
        assert float(metrics["used_arena"]) == float(use_arena)
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5, rtol=1e-5)


def test_vmem_gate():
    """The fused K-step kernel is gated on its documented VMEM budget; a
    width past the cap must refuse (the round then takes the scan path)."""
    assert fits_vmem(256)
    too_wide = 2048
    assert vmem_bytes(too_wide) > VMEM_CAP_BYTES and not fits_vmem(too_wide)
    with pytest.raises(AssertionError, match="VMEM"):
        from repro.kernels.inner_loop import inner_loop_affine_pallas
        z = jnp.zeros((2, too_wide))
        inner_loop_affine_pallas(z, jnp.zeros((2, too_wide, too_wide)), z,
                                 jnp.zeros((too_wide,)), z, 0.1, 1.0, 2,
                                 interpret=True)


def test_svrg_uses_scan_path_with_native_oracle():
    """SVRG (per-step batches) cannot use the fused affine kernel but still
    runs the arena-native oracle in the scan -- parity vs the pytree path."""
    key = jax.random.key(5)
    m, d, K = 4, 16, 3
    params = jnp.zeros((d,))
    batch = {"w": jax.random.normal(key, (K, m, d))}

    def plain(x, b):
        return 0.3 * x + 0.01 * b["w"]

    from repro.core.api import make_oracle
    native = make_oracle(plain, grad_arena=lambda spec: (
        lambda xa, b: 0.3 * xa + jnp.pad(0.01 * b["w"], ((0, 0), (0, spec.width - d)))
        if spec.width != d else 0.3 * xa + 0.01 * b["w"]))

    outs = {}
    for use_arena, grad in [(True, native), (False, plain)]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=K, eta=0.1,
                                   variance_reduction="svrg", use_arena=use_arena))
        s = opt.init(params, m)
        for _ in range(3):
            s, _ = opt.round(s, grad, batch, per_step_batches=True)
        outs[use_arena] = np.asarray(s["x_s"])
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-6)


# ---------------------------------------------------------------------------
# round-batched scan driver == loop of rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", [{}, {"participation": 0.5},
                                     {"uplink_bits": 8}],
                         ids=["plain", "partial", "ef21"])
def test_scan_rounds_equals_loop(prob, variant):
    """R rounds inside one lax.scan land on the SAME state as R separate
    round calls (incl. the round-counter-folded participation RNG)."""
    R = 4
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.5 / prob.L,
                          use_arena=True, **variant)
    opt = make(cfg)
    grad = prob.oracle()
    batch = prob.batch()

    s_loop = opt.init(jnp.zeros((prob.d,)), prob.m)
    per_round_metrics = []
    for _ in range(R):
        s_loop, mets = opt.round(s_loop, grad, batch)
        per_round_metrics.append(mets)

    scan = make_scan_rounds(opt, grad)
    batches = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), batch)
    s_scan, stacked = scan(opt.init(jnp.zeros((prob.d,)), prob.m), batches)

    # eager loop vs one traced scan: XLA fusion reorders the f32 math, so
    # exact bitwise equality is not the contract -- tight allclose is
    for k in s_loop:
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(s_loop[k])[0]),
            np.asarray(jax.tree.leaves(s_scan[k])[0]),
            atol=1e-4, rtol=1e-4, err_msg=f"state[{k}]")
    # metrics come back stacked (R,), matching the per-round values
    for k in stacked:
        got = np.asarray(stacked[k])
        assert got.shape[0] == R
        if k == "lam_sum_norm":  # KKT invariant: exactly-0 up to f32 noise,
            assert np.all(got < 1e-3)  # noise-vs-noise closeness is meaningless
            continue
        want = np.asarray([float(mm[k]) for mm in per_round_metrics])
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3,
                                   err_msg=f"metrics[{k}]")


# ---------------------------------------------------------------------------
# built-in oracles: softmax closed form, ridge quadratics
# ---------------------------------------------------------------------------

def test_softmax_grad_matches_autodiff():
    sm = SoftmaxRegression(n_features=12, n_classes=3)
    w = jax.random.normal(jax.random.key(1), (sm.dim,))
    b = {"x": jax.random.normal(jax.random.key(2), (20, 12)),
         "y": jax.random.randint(jax.random.key(3), (20,), 0, 3)}
    np.testing.assert_allclose(np.asarray(sm.grad(w, b)),
                               np.asarray(jax.grad(sm.loss)(w, b)), atol=1e-5)


def test_softmax_arena_round_parity():
    """The arena-native softmax gradient drives rounds identical to the
    pytree path (the Table I experiment's hot path)."""
    sm = SoftmaxRegression(n_features=12, n_classes=3)
    m = 4
    key = jax.random.key(7)
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 0), (m, 20, 12)),
             "y": jax.random.randint(jax.random.fold_in(key, 1), (m, 20), 0, 3)}
    outs = {}
    for use_arena, grad in [(True, sm.oracle()), (False, sm.grad)]:
        opt = make(FederatedConfig(algorithm="agpdmm", inner_steps=3, eta=0.1,
                                   use_arena=use_arena))
        s = opt.init(sm.init_params(), m)
        for _ in range(3):
            s, _ = opt.round(s, grad, batch)
        outs[use_arena] = np.asarray(s["x_s"])
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5, rtol=1e-5)


def test_ridge_quadratic():
    """with_ridge: optimum/constants are recomputed and the affine oracle
    carries the reg term -- grad of F at x* vanishes, rounds still agree."""
    base = quadratic.generate(jax.random.key(2), m=4, n=40, d=10)
    pr = base.with_ridge(0.7)
    assert pr.L == pytest.approx(base.L + 0.7) and pr.mu == pytest.approx(base.mu + 0.7)
    total_grad = (jnp.einsum("mde,e->d", pr.AtA, pr.x_star) - pr.Atb.sum(0)
                  + pr.m * pr.reg * pr.x_star)
    assert float(jnp.linalg.norm(total_grad)) < 1e-2
    assert float(pr.gap(pr.x_star)) == pytest.approx(0.0, abs=1e-2)
    outs = {}
    for use_arena, grad in [(True, pr.oracle()), (False, pr.grad)]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=3, eta=0.5 / pr.L,
                                   use_arena=use_arena))
        s = opt.init(jnp.zeros((pr.d,)), pr.m)
        for _ in range(6):
            s, _ = opt.round(s, grad, pr.batch())
        outs[use_arena] = np.asarray(s["x_s"])
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5, rtol=1e-5)
    # the rounds actually make progress toward the ridge optimum
    assert np.linalg.norm(outs[True] - np.asarray(pr.x_star)) < np.linalg.norm(
        np.asarray(pr.x_star))


# ---------------------------------------------------------------------------
# satellite contracts: participation seed, auto arena dispatch
# ---------------------------------------------------------------------------

def test_participation_seed_contract():
    """Identical seeds -> identical masks ACROSS algorithms (a contract now,
    not an accident of a duplicated constant); different seeds differ."""
    m = 8
    g = FederatedConfig(algorithm="gpdmm", participation=0.5, seed=3)
    a = FederatedConfig(algorithm="agpdmm", participation=0.5, seed=3)
    other = FederatedConfig(algorithm="gpdmm", participation=0.5, seed=4)
    rounds = jnp.arange(6)
    masks = {
        name: np.asarray([T.participation_mask(participation_key(c, r), m, 0.5)
                          for r in rounds])
        for name, c in [("g", g), ("a", a), ("other", other)]
    }
    np.testing.assert_array_equal(masks["g"], masks["a"])
    assert not np.array_equal(masks["g"], masks["other"])


def test_seed_changes_partial_rounds(prob):
    cfgs = [FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.5 / prob.L,
                            participation=0.5, seed=s) for s in (3, 3, 9)]
    finals = []
    for cfg in cfgs:
        opt = make(cfg)
        s = opt.init(jnp.zeros((prob.d,)), prob.m)
        for _ in range(3):
            s, _ = opt.round(s, prob.oracle(), prob.batch())
        finals.append(np.asarray(s["x_s"]))
    np.testing.assert_array_equal(finals[0], finals[1])  # same seed: bitwise
    assert not np.allclose(finals[0], finals[2])  # different seed: different rounds


def test_auto_arena_dispatch():
    """use_arena="auto": tiny widths keep the pytree layout, wide ones pack;
    the decision is visible in round metrics (used_arena)."""
    def grad(p, _b):
        return jax.tree.map(lambda x: 0.3 * x, p)

    m, batch = 4, {"d": jnp.zeros((4, 1))}
    for params, expect_arena in [
        ({"w": jnp.ones((24,))}, False),  # width 128 < arena_min_width
        ({"w": jnp.ones((4000,))}, True),  # width 4096
    ]:
        cfg = FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.1)
        assert cfg.use_arena == "auto"
        opt = make(cfg)
        s = opt.init(params, m)
        # arena keeps clients packed as ONE (m, width) buffer; the pytree
        # path preserves the dict structure
        assert isinstance(s["lam_s"], jax.Array) == expect_arena
        s, metrics = opt.round(s, grad, batch)
        assert float(metrics["used_arena"]) == float(expect_arena)
