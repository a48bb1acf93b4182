"""Mixed-precision training: weights stored in ``ArchConfig.state_dtype``,
computed in ``ArchConfig.dtype`` (OLMo-1B stores float32 and computes in
bfloat16, as the OLMo paper trains it).

Covers, on the CPU at small widths: the program's loss and gradient against
the plain float32 reference, that its matmuls take bfloat16 operands, that
float32 state keeps the GPDMM updates a bfloat16 state rounds away, and
that no other architecture's parameters change dtype.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from repro.configs import ARCHS, get_arch
from repro.configs.base import FederatedConfig
from repro.core import arena, make
from repro.models import build
from repro.models.model import compute_params

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:  # the benchmark's plain reference
    sys.path.insert(0, str(REPO))
from chipbench.reference import gpdmm as ref_gpdmm  # noqa: E402
from chipbench.reference import olmo as ref_olmo  # noqa: E402

SMALL = dict(d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
             vocab_size=512, n_layers=2)
SEQ = 64


def small_olmo(**kw):
    return dataclasses.replace(get_arch("olmo-1b"), **SMALL, **kw)


def scale_002_params(model, seed=7):
    """The model's parameter tree with every weight N(0, 0.02), as the
    benchmark draws OLMo's (OLMo's init std)."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        (jax.random.normal(k, s.shape, jnp.float32) * 0.02).astype(s.dtype)
        for k, s in zip(keys, leaves)])


def tokens(m=None, seed=3):
    lead = () if m is None else (m,)
    t = jax.random.randint(jax.random.key(seed), lead + (3, SEQ + 1), 0, SMALL["vocab_size"])
    return {"tokens": t[..., :-1], "targets": t[..., 1:]}


def test_olmo_recipe_is_float32_state_bfloat16_compute():
    cfg = get_arch("olmo-1b")
    assert (cfg.resolved_state_dtype, cfg.dtype) == ("float32", "bfloat16")
    shapes = jax.eval_shape(build(cfg).init, jax.random.key(0))
    assert {s.dtype for s in jax.tree.leaves(shapes)} == {jnp.dtype(jnp.float32)}


def test_mixed_loss_and_grad_match_float32_reference():
    """Loss and gradient with float32 state and bfloat16 compute against the
    plain float32 reference (matmuls at ``highest``).  bfloat16 keeps 8
    significant bits, so each rounding is up to 2^-9 = 0.2 % of the value;
    the gradient passes a few of them per layer and lands near 1 % (0.6 to
    0.85 % per leaf here), so each leaf may differ by 3 %.  The loss, about
    log(vocab), is dominated by the logsumexp taken in float32: 1e-3."""
    model = build(small_olmo())
    params = scale_002_params(model)
    b = tokens()
    loss, grad = jax.value_and_grad(lambda p: model.loss(p, b)[0])(params)
    cfg = dict(SMALL, norm_eps=1e-6, rope_theta=10_000.0)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grad = jax.value_and_grad(lambda p: ref_olmo.loss(
            cfg, p, b["tokens"], b["targets"]))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-3 * float(ref_loss)
    assert jax.tree.structure(grad) == jax.tree.structure(ref_grad)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree.leaves(ref_grad)):
        assert g.dtype == jnp.float32, (jax.tree_util.keystr(path), g.dtype)
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err <= 3e-2, (jax.tree_util.keystr(path), err)


def test_embedding_gradient_accumulates_in_the_state_dtype():
    """A token at every position: its embedding row's gradient sums one
    contribution per position (the lookup's scatter-add).  The lookup reads
    the float32 table and casts the rows it gathers, so the sum runs in
    float32 and meets the reference's to bfloat16 rounding of each term;
    summed in bfloat16 it would drift low by a few percent."""
    model = build(small_olmo())
    params = scale_002_params(model)
    t = jnp.full((3, SEQ + 1), 5, jnp.int32)
    b = {"tokens": t[:, :-1], "targets": t[:, 1:]}
    grad = jax.grad(lambda p: model.loss(p, b)[0])(params)["embed"]["w"][5]
    cfg = dict(SMALL, norm_eps=1e-6, rope_theta=10_000.0)
    with jax.default_matmul_precision("highest"):
        ref = jax.grad(lambda p: ref_olmo.loss(cfg, p, b["tokens"], b["targets"]))(
            params)["embed"]["w"][5]
    assert float(jnp.linalg.norm(grad - ref) / jnp.linalg.norm(ref)) <= 1e-2


def _dots(jaxpr):
    """(lhs dtype, rhs dtype, shapes of lhs, rhs and output) of every
    dot_general in ``jaxpr`` and the jaxprs nested in it (scans, remat)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs, rhs = eqn.invars[:2]
            out.append((lhs.aval.dtype, rhs.aval.dtype,
                        {lhs.aval.shape, rhs.aval.shape, eqn.outvars[0].aval.shape}))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dots(sub)
    return out


def test_mixed_matmuls_take_bfloat16_operands():
    """Every matmul with a weight, forward (x W) and backward (dx = dy W^T,
    dW = x^T dy), reads bfloat16 operands: those whose operands or result
    have a weight's shape, per layer inside the layer scan or whole.  A
    change that quietly computes them in float32 fails here.  (Attention's
    score and value products and the loss's one-hot contraction read
    activations only and are the attention's and the loss's own choice.)"""
    model = build(small_olmo())
    params = scale_002_params(model)
    b = tokens()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.loss(p, b)[0]))(params).jaxpr
    weights = {s for a in jax.tree.leaves(params)
               for s in (a.shape, a.shape[1:], a.shape[::-1])}  # the head reads W^T
    with_weight = [d for d in _dots(jaxpr) if d[2] & weights]
    assert all(d[:2] == (jnp.bfloat16, jnp.bfloat16) for d in with_weight), with_weight
    # 7 weights a layer, each in 3 products, and the tied head in 3
    assert len(with_weight) >= 7 * 3 + 3, len(with_weight)


def test_param_cast_is_named_and_once():
    """The cast to the compute dtype sits under ``model.param_cast``, one
    convert per weight, at the forward's entry."""
    model = build(small_olmo())
    params = scale_002_params(model)
    text = jax.jit(lambda p: model.loss(p, tokens())[0]).lower(params).as_text(
        debug_info=True)
    assert "model.param_cast" in text
    cast = compute_params(model.cfg, params)
    assert {a.dtype for a in jax.tree.leaves(cast)} == {jnp.dtype(jnp.bfloat16)}


def test_float32_state_keeps_updates_bfloat16_state_loses():
    """One GPDMM round on a scale-0.02 tree at the benchmark's stepsize
    (eta 0.05, K = 2: step 1/30): most updates are below the bfloat16
    spacing of the weights, so a bfloat16 state leaves most elements
    unchanged, where the float32 state moves nearly all of them."""
    fed = make(FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.05,
                               num_clients=2, layout="client_axis"))
    batch = tokens(m=2)
    changed = {}
    for state_dtype in ("float32", "bfloat16"):
        model = build(small_olmo(state_dtype=state_dtype))
        p0 = scale_002_params(model)
        grad = lambda p, b, model=model: jax.grad(lambda q: model.loss(q, b)[0])(p)  # noqa: E731
        state, metrics = jax.jit(lambda s, b: fed.round(s, grad, b))(fed.init(p0, 2), batch)
        assert float(metrics["used_arena"]) == 1.0
        assert state["x_c"].dtype == jnp.dtype(state_dtype)
        x0 = arena.ArenaSpec.from_tree(p0).pack(p0)
        changed[state_dtype] = float(jnp.mean(state["x_c"] != x0[None]))
    assert changed["float32"] > 0.999, changed
    assert changed["bfloat16"] < 0.5, changed


def test_rounds_match_reference_driven_by_the_programs_gradient():
    """Three GPDMM rounds of the program (float32 arena, bfloat16 compute,
    each client stepped as its gradient is made) against the plain
    reference round driven by the same gradient function: the eq. (20)
    steps with their duals, the uplink and the dual refresh agree to
    float32 rounding, element by element, in rounds 2 and 3 too, where the
    duals are not zero.  The same reference with round 3's duals zeroed
    misses by far more: a round that dropped them would fail here."""
    model = build(small_olmo())
    p0 = scale_002_params(model)
    fed = make(FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.05,
                               num_clients=2, layout="client_axis"))
    grad = lambda p, b: jax.grad(lambda q: model.loss(q, b)[0])(p)  # noqa: E731
    batches = [tokens(m=2, seed=s) for s in (3, 4, 5)]
    spec = arena.ArenaSpec.from_tree(p0)
    rf = jax.jit(lambda s, b: ref_gpdmm.round_fn(
        s, b, grad, K=2, eta=0.05, per_step=False, store=jnp.float32))
    prog = jax.jit(lambda s, b: fed.round(s, grad, b))

    def flat(s):
        return np.asarray(jnp.concatenate([spec.pack(s["x_s"])[None], s["x_c"], s["lam"]]))

    state, ref = fed.init(p0, 2), ref_gpdmm.init(p0, 2)
    for r, b in enumerate(batches):
        if r == 2:
            dropped, _ = rf(dict(ref, lam=jax.tree.map(jnp.zeros_like, ref["lam"])), b)
        state, _ = prog(state, b)
        ref, _ = rf(ref, b)
        got = flat({"x_s": state["x_s"], "x_c": state["x_c"], "lam": state["lam_s"]})
        want = flat({"x_s": ref["x_s"], "x_c": spec.pack_stacked(ref["x_c"]),
                     "lam": spec.pack_stacked(ref["lam"])})
        err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
        assert err.max() <= 1e-5, (r, err)
    miss = flat({"x_s": dropped["x_s"], "x_c": spec.pack_stacked(dropped["x_c"]),
                 "lam": spec.pack_stacked(dropped["lam"])})
    assert (np.linalg.norm(miss - want, axis=-1) / np.linalg.norm(want, axis=-1)).max() > 1e-2


def test_remat_saves_weight_matmul_outputs_and_keeps_the_gradient(capsys):
    """Each checkpointed layer keeps the outputs of its weight matmuls (the
    q/k/v/o projections, the MLP's up and gate matmuls) for the backward
    pass and recomputes the rest, attention's score and value products
    included; the gradient is the one the model gives without remat, to
    the bit on the CPU.  The residuals are listed with the layers unrolled,
    so that each names the function that made it."""
    b = tokens()
    params = scale_002_params(build(small_olmo()))
    grads = {}
    for remat in (True, False):
        model = build(small_olmo(remat=remat))
        grads[remat] = jax.jit(jax.grad(lambda p, model=model: model.loss(p, b)[0]))(params)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads[True]),
                            jax.tree.leaves(grads[False])):
        err = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
        assert err <= 1e-6, (jax.tree_util.keystr(path), err)
    unrolled = build(small_olmo(scan_layers=False))
    print_saved_residuals(lambda p: unrolled.loss(p, b)[0], params)
    saved = capsys.readouterr().out.splitlines()
    layers = SMALL["n_layers"]
    # q, k, v and the attention output projection; the MLP's up matmul and
    # its gate (saved past the SiLU, which is what the backward reads)
    assert sum("(gqa_apply)" in line for line in saved) == 4 * layers, saved
    assert sum("(mlp_apply)" in line for line in saved) == 2 * layers, saved
    assert not any("_flash_xla" in line for line in saved), saved


def test_cast_weights_are_not_cast_again():
    """Serving casts the stored weights to the compute dtype once
    (``launch/serve``); the forward's cast then leaves them as they are."""
    cfg = small_olmo()
    cast = compute_params(cfg, scale_002_params(build(cfg)))
    assert not jax.make_jaxpr(lambda p: compute_params(cfg, p))(cast).eqns


@pytest.mark.parametrize("name", sorted(n for n in ARCHS if n != "olmo-1b"))
def test_other_archs_store_what_they_compute(name):
    """The stored dtype defaults to the compute dtype: every other
    architecture's parameter tree is the one it had before the field, and
    its forward casts nothing."""
    cfg = ARCHS[name]
    assert cfg.state_dtype is None and cfg.resolved_state_dtype == cfg.dtype
    got = jax.eval_shape(build(cfg).init, jax.random.key(0))
    same = jax.eval_shape(build(dataclasses.replace(cfg, state_dtype=cfg.dtype)).init,
                          jax.random.key(0))
    assert jax.tree.map(lambda s: s.dtype, got) == jax.tree.map(lambda s: s.dtype, same)
    assert {s.dtype for s in jax.tree.leaves(got)} <= {jnp.dtype(cfg.dtype),
                                                      jnp.dtype(jnp.float32)}
    small = cfg.reduced()
    params = jax.eval_shape(build(small).init, jax.random.key(0))
    assert compute_params(small, params) is params
    np.testing.assert_equal(small.resolved_state_dtype, small.dtype)
