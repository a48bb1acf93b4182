"""Core federated-optimiser tests: every analytical claim of the paper that
can be checked numerically on small problems."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederatedConfig
from repro.core import arena, fedsplit, make, pdmm, quadratic
from repro.core import tree_util as T


@pytest.fixture(scope="module")
def prob():
    return quadratic.generate(jax.random.key(0), m=8, n=120, d=24)


@pytest.fixture(scope="module")
def x0(prob):
    return jnp.zeros((prob.d,))


def jit_round(opt, oracle, batch, **kw):
    @jax.jit
    def f(s):
        return opt.round(s, oracle, batch, **kw)
    return f


# ---------------------------------------------------------------------------
# SSIII-B: PDMM == FedSplit on the star graph (exact, prox oracle)
# ---------------------------------------------------------------------------

def test_pdmm_equals_fedsplit_exact(prob, x0):
    cfg = FederatedConfig(rho=200.0)
    prox = prob.make_client_prox()
    p = pdmm.make_exact(cfg)
    f = fedsplit.make_exact(cfg)
    sp, sf = p.init(x0, prob.m), f.init(x0, prob.m)
    for r in range(15):
        sp, _ = p.round(sp, prox)
        sf, _ = f.round(sf, prox)
        np.testing.assert_allclose(
            np.asarray(sp["x_s"]), np.asarray(sf["x_s"]), rtol=0, atol=1e-5,
            err_msg=f"trajectories diverge at round {r}",
        )
    # and both converge to the optimum
    assert float(prob.gap(sp["x_s"])) < 1e-2


# ---------------------------------------------------------------------------
# eqs. (27)/(31): K=1 AGPDMM == SCAFFOLD == FedAvg == vanilla GD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["agpdmm", "scaffold", "fedavg"])
def test_k1_reduces_to_gd(prob, x0, algo):
    eta = 0.5 / prob.L
    opt = make(FederatedConfig(algorithm=algo, inner_steps=1, eta=eta))
    s = opt.init(x0, prob.m)
    batch = prob.batch()
    for _ in range(8):
        s, _ = opt.round(s, prob.grad, batch)
    xg = x0
    for _ in range(8):
        g = (jnp.einsum("mde,e->d", prob.AtA, xg) - prob.Atb.sum(0)) / prob.m
        xg = xg - eta * g
    np.testing.assert_allclose(np.asarray(opt.server_params(s)), np.asarray(xg), atol=5e-4)


# ---------------------------------------------------------------------------
# Fig. 1: Inexact FedSplit with the improper z-init stalls; x_s-init converges
# ---------------------------------------------------------------------------

def test_inexact_fedsplit_bad_init_stalls(prob, x0):
    eta = 1.0 / prob.L
    gaps = {}
    for init in ["z", "xs"]:
        opt = make(FederatedConfig(algorithm="fedsplit", inner_steps=3, eta=eta,
                                   fedsplit_init=init, rho=prob.L / 10))
        s = opt.init(x0, prob.m)
        rf = jit_round(opt, prob.grad, prob.batch())
        for _ in range(200):
            s, _ = rf(s)
        gaps[init] = float(prob.gap(s["x_s"]))
    # xs-init reaches the f32 gap floor; z-init stalls an order of magnitude
    # (or more) above it -- the paper's Fig. 1 contrast
    assert gaps["xs"] < 1e-2, gaps
    assert gaps["z"] > 10 * max(gaps["xs"], 1e-6), f"bad init should stall: {gaps}"


# ---------------------------------------------------------------------------
# GPDMM / AGPDMM converge where FedAvg drifts (K > 1, heterogeneous clients)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm", "scaffold"])
def test_k5_converges(prob, x0, algo):
    opt = make(FederatedConfig(algorithm=algo, inner_steps=5, eta=0.5 / prob.L))
    s = opt.init(x0, prob.m)
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(150):
        s, metrics = rf(s)
    assert float(prob.gap(opt.server_params(s))) < 1e-2


def test_k5_fedavg_drifts(prob, x0):
    opt = make(FederatedConfig(algorithm="fedavg", inner_steps=5, eta=0.5 / prob.L))
    s = opt.init(x0, prob.m)
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(150):
        s, _ = rf(s)
    # FedAvg stalls at a heterogeneity-dependent plateau
    assert float(prob.gap(opt.server_params(s))) > 1e-2


# ---------------------------------------------------------------------------
# eq. (25): sum_i lam_{s|i} == 0 invariant, every round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
def test_dual_sum_invariant(prob, x0, algo):
    opt = make(FederatedConfig(algorithm=algo, inner_steps=3, eta=0.5 / prob.L))
    s = opt.init(x0, prob.m)
    for _ in range(20):
        s, metrics = opt.round(s, prob.grad, prob.batch())
        assert float(metrics["lam_sum_norm"]) < 1e-3


# ---------------------------------------------------------------------------
# Remark 1: last-iterate dual update (eq. 24) converges at least as fast
# ---------------------------------------------------------------------------

def test_gpdmm_last_iterate_variant(prob, x0):
    """Both the eq.-(23) average and eq.-(24) last-iterate dual updates
    converge; at a mid-trajectory checkpoint the last-iterate variant is at
    least comparable (Remark 1).  Distances, not f32 functional gaps."""
    dist = {}
    for use_avg in [True, False]:
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=5,
                                   eta=0.5 / prob.L, use_avg=use_avg))
        s = opt.init(x0, prob.m)
        rf = jit_round(opt, prob.grad, prob.batch())
        for _ in range(15):
            s, _ = rf(s)
        dist[use_avg] = float(jnp.linalg.norm(opt.server_params(s) - prob.x_star))
    assert dist[False] <= dist[True] * 1.5, dist
    assert dist[False] < 1.0 and dist[True] < 1.0, dist


# ---------------------------------------------------------------------------
# Alg. 2 as Alg. 1: AGPDMM runs through GPDMM's round bodies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["pytree", "arena", "cohort"])
def test_agpdmm_is_gpdmm_restarted_at_the_server(prob, x0, layout):
    """AGPDMM (Alg. 2) is GPDMM with the last-iterate uplink (eq. 24) whose
    carry restarts at the broadcast server row every round: three rounds,
    bitwise, on every layout."""
    kw = dict(inner_steps=3, eta=0.5 / prob.L, use_arena=layout != "pytree")
    if layout == "cohort":
        kw.update(participation=0.5, cohort=True)
    acc = make(FederatedConfig(algorithm="agpdmm", **kw))
    gp = make(FederatedConfig(algorithm="gpdmm", use_avg=False, **kw))
    grad = prob.grad if layout == "pytree" else prob.oracle()
    s_a, s_g = acc.init(x0, prob.m), gp.init(x0, prob.m)
    for r in range(3):
        x_s = gp.server_params(s_g)
        if layout == "pytree":
            x_c = T.tree_broadcast(x_s, prob.m)
        else:
            row = arena.ArenaSpec.from_tree(x_s).pack(x_s)
            x_c = jnp.broadcast_to(row[None], s_g["x_c"].shape)
        s_a, m_a = acc.round(s_a, grad, prob.batch())
        s_g, m_g = gp.round(dict(s_g, x_c=x_c), grad, prob.batch())
        assert set(s_g) - set(s_a) == {"x_c"}
        for k in s_a:
            np.testing.assert_array_equal(np.asarray(s_a[k]), np.asarray(s_g[k]),
                                          err_msg=f"round {r}: {k}")
        for k in m_a:
            np.testing.assert_array_equal(np.asarray(m_a[k]), np.asarray(m_g[k]),
                                          err_msg=f"round {r}: {k}")


# ---------------------------------------------------------------------------
# AGPDMM beats GPDMM for K > 1 (the paper's headline experiment ordering)
# ---------------------------------------------------------------------------

def test_agpdmm_faster_than_gpdmm(prob, x0):
    gaps = {}
    for algo in ["gpdmm", "agpdmm"]:
        opt = make(FederatedConfig(algorithm=algo, inner_steps=5, eta=0.5 / prob.L))
        s = opt.init(x0, prob.m)
        rf = jit_round(opt, prob.grad, prob.batch())
        for _ in range(40):
            s, _ = rf(s)
        gaps[algo] = float(prob.gap(opt.server_params(s)))
    assert gaps["agpdmm"] <= gaps["gpdmm"], gaps


# ---------------------------------------------------------------------------
# per-step minibatches path (paper's softmax-regression regime)
# ---------------------------------------------------------------------------

def test_per_step_batches(prob, x0):
    K = 3
    opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=K, eta=0.5 / prob.L))
    s = opt.init(x0, prob.m)
    batch = prob.batch()
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), batch)
    rf = jit_round(opt, prob.grad, stacked, per_step_batches=True)
    for _ in range(30):
        s, _ = rf(s)
    # identical batches per step == shared-batch behaviour
    opt2 = make(FederatedConfig(algorithm="gpdmm", inner_steps=K, eta=0.5 / prob.L))
    s2 = opt2.init(x0, prob.m)
    rf2 = jit_round(opt2, prob.grad, batch)
    for _ in range(30):
        s2, _ = rf2(s2)
    np.testing.assert_allclose(np.asarray(s["x_s"]), np.asarray(s2["x_s"]), atol=1e-5)


# ---------------------------------------------------------------------------
# beyond-paper: EF21 delta-quantised uplink (SSPerf H3) still converges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_uplink_converges(prob, x0, algo, bits):
    """EF21-style delta-compressed uplink: each client transmits
    q(u_i - u_hat_i) and both sides integrate, so the quantisation scale
    shrinks with the residual and convergence matches the exact method --
    extending the paper's one-variable-per-direction claim from 16 to as few
    as 4 bits/param on the wire."""
    opt = make(FederatedConfig(algorithm=algo, inner_steps=5, eta=0.5 / prob.L,
                               uplink_bits=bits))
    s = opt.init(x0, prob.m)
    assert "u_hat" in s
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(150):
        s, metrics = rf(s)
    assert float(metrics["lam_sum_norm"]) < 1e-3  # eq. (25) survives quantisation
    assert float(prob.gap(opt.server_params(s))) < 1e-2


def test_quantized_uplink_delta_encoding_matters(prob, x0):
    """Directly quantising the uplink (no delta integrator) stalls at the
    quantisation floor: PDMM's duals integrate the per-round rounding error.
    Emulated by resetting u_hat to the round-0 view each round."""
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=5, eta=0.5 / prob.L,
                          uplink_bits=8)
    opt = make(cfg)

    s_d = opt.init(x0, prob.m)
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(200):
        s_d, _ = rf(s_d)

    s_no = opt.init(x0, prob.m)
    u0 = s_no["u_hat"]

    @jax.jit
    def rf_no(s):
        s, m = opt.round(s, prob.grad, prob.batch())
        s["u_hat"] = u0  # kill the integrator -> direct quantisation of u - u0
        return s, m

    for _ in range(200):
        s_no, _ = rf_no(s_no)

    gap_d = float(prob.gap(opt.server_params(s_d)))
    gap_no = float(prob.gap(opt.server_params(s_no)))
    # delta-encoded converges below tolerance; direct quantisation stalls
    # above it (f32 functional gaps quantise to ~2e-3 steps, so compare
    # against the tolerance rather than a ratio)
    assert gap_d < 1e-2 <= gap_no, (gap_d, gap_no)


# ---------------------------------------------------------------------------
# property: the optimisers are structure-preserving pytree transformations
# ---------------------------------------------------------------------------

from _hyp import given, settings, st  # noqa: E402  (skips cleanly w/o hypothesis)


@st.composite
def _pytrees(draw):
    """Random nested-dict pytrees of small float arrays."""
    n_leaves = draw(st.integers(1, 4))
    tree = {}
    for i in range(n_leaves):
        shape = tuple(draw(st.lists(st.integers(1, 5), min_size=1, max_size=3)))
        tree[f"w{i}"] = jnp.full(shape, float(i + 1))
    if draw(st.booleans()):
        tree = {"nested": tree, "bias": jnp.zeros((3,))}
    return tree


@settings(max_examples=10, deadline=None)
@given(params=_pytrees(), algo=st.sampled_from(["gpdmm", "agpdmm", "scaffold", "fedavg"]),
       m=st.integers(2, 4), k=st.integers(1, 3))
def test_round_preserves_structure_and_invariants(params, algo, m, k):
    """For ANY parameter pytree: one round preserves the state structure,
    keeps every leaf finite, and (for the PDMM family) keeps sum_i lam = 0."""
    opt = make(FederatedConfig(algorithm=algo, inner_steps=k, eta=0.1))

    def grad_fn(p, _b):
        return jax.tree.map(lambda x: 0.3 * x, p)  # grad of 0.15||x||^2

    batch = {"dummy": jnp.zeros((m, 1))}
    s = opt.init(params, m)
    s2, metrics = opt.round(s, grad_fn, batch)
    assert jax.tree.structure(s2) == jax.tree.structure(s)
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(s2)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.isfinite(jnp.asarray(b, jnp.float32)).all())
    if algo in ("gpdmm", "agpdmm"):
        assert float(metrics["lam_sum_norm"]) < 1e-4
    # server params move toward 0 for this strongly-convex objective
    before = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(params))
    after = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(opt.server_params(s2)))
    assert after <= before + 1e-6


# ---------------------------------------------------------------------------
# beyond-paper: partial client participation (async PDMM)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["gpdmm", "agpdmm"])
def test_partial_participation_converges(prob, x0, algo):
    """With only half the clients active per round (async PDMM: the server
    reuses its cached uplink view of silent clients), the method still
    converges, and the KKT invariant (25) survives partial rounds exactly
    because lam_{s|i} is recomputed server-side for ALL i."""
    opt = make(FederatedConfig(algorithm=algo, inner_steps=5, eta=0.5 / prob.L,
                               participation=0.5))
    s = opt.init(x0, prob.m)
    assert "u_hat" in s
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(400):  # ~2x the full-participation rounds
        s, metrics = rf(s)
    assert float(metrics["lam_sum_norm"]) < 1e-3
    assert float(prob.dist(opt.server_params(s))) < 1e-2


def test_partial_participation_composes_with_quantization(prob, x0):
    """participation=0.5 + 8-bit EF21 uplink together still converge."""
    opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=5,
                               eta=0.5 / prob.L,
                               participation=0.5, uplink_bits=8))
    s = opt.init(x0, prob.m)
    rf = jit_round(opt, prob.grad, prob.batch())
    for _ in range(400):
        s, metrics = rf(s)
    assert float(metrics["lam_sum_norm"]) < 1e-3
    assert float(prob.dist(opt.server_params(s))) < 5e-2


# ---------------------------------------------------------------------------
# beyond-paper: SVRG variance reduction (the paper's SSVII future work)
# ---------------------------------------------------------------------------

def test_svrg_beats_plain_stochastic_gpdmm():
    """With noisy per-step minibatch gradients, plain GPDMM stalls at a noise
    ball; the SVRG-corrected variant keeps contracting toward x*."""
    key = jax.random.key(5)
    m, n, d, K = 8, 128, 24, 4
    A = jax.random.normal(key, (m, n, d))
    y0 = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    b = jnp.einsum("mnd,d->mn", A, y0) + 0.5 * jax.random.normal(
        jax.random.fold_in(key, 2), (m, n))
    AtA = jnp.einsum("mnd,mne->mde", A, A)
    Atb = jnp.einsum("mnd,mn->md", A, b)
    x_star = jnp.linalg.solve(AtA.sum(0), Atb.sum(0))
    L = float(jnp.linalg.eigvalsh(AtA).max())

    # K row-chunks per client; x K so each chunk gradient is an unbiased
    # estimate of the client's full gradient
    Ac = A.reshape(m, K, n // K, d).swapaxes(0, 1)  # (K, m, n/K, d)
    bc = b.reshape(m, K, n // K).swapaxes(0, 1)
    batch = {
        "AtA": jnp.einsum("kmnd,kmne->kmde", Ac, Ac) * K,
        "Atb": jnp.einsum("kmnd,kmn->kmd", Ac, bc) * K,
    }

    def grad_fn(x, bt):
        return bt["AtA"] @ x - bt["Atb"]

    def run(vr):
        opt = make(FederatedConfig(algorithm="gpdmm", inner_steps=K,
                                   eta=0.5 / L, variance_reduction=vr))
        s = opt.init(jnp.zeros((d,)), m)

        @jax.jit
        def rf(s):
            s, _ = opt.round(s, grad_fn, batch, per_step_batches=True)
            return s

        for _ in range(200):
            s = rf(s)
        return float(jnp.linalg.norm(opt.server_params(s) - x_star))

    d_plain = run(None)
    d_svrg = run("svrg")
    # chunk gradients differ from the full gradient (row noise), so plain
    # per-step GPDMM stalls above the svrg variant by a clear margin
    assert d_svrg < d_plain / 3, (d_svrg, d_plain)
    assert d_svrg < 1e-3, d_svrg
