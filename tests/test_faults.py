"""ISSUE 6: fault-tolerant rounds -- deterministic fault injection, fused
uplink screening, and the demotion == silence contract.

The load-bearing invariant: a faulted + screened round is BIT-IDENTICAL to a
participation-masked round with the same effective mask.  Because
``faults.plan`` is a pure function of (fault seed, round, client), the tests
precompute each round's draw, derive the mask a perfect screen would
produce (active & ~silent & ~corrupt), monkeypatch
``tree_util.participation_mask`` in a fault-free reference run to return
exactly that mask, and assert whole-state bitwise equality across all four
centralised algorithms (arena AND pytree paths) plus the dropout-only graph
analogue.  Identical clients make the demotion guarantee exact: honest
deviations are bitwise equal, so the round median is exact and every
corrupted row (NaN/Inf by the finite flag; sign/blowup by deviation) is
demoted while no honest row ever is.

Also here: interpret-mode + hypothesis parity for the fused screen kernel,
the all-silent round as a well-defined no-op, same-seed fault-trace
determinism, checkpoint retention/truncation, and the config validators.
"""
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.checkpoint import msgpack_ckpt as ckpt
from repro.configs.base import FaultConfig, FederatedConfig
from repro.core import arena, faults, make, quadratic
from repro.core import tree_util as T
from repro.kernels import ops

ALGOS = ["gpdmm", "agpdmm", "scaffold", "fedavg"]
M = 8
D = 24  # packs to one 128-lane arena row


def _params():
    return {"w": 0.7 * jnp.ones((D,), jnp.float32)}


def _grad(p, b):
    # identical linear clients: every honest uplink is bitwise equal, so the
    # screen's round median is exact and demotion is all-or-nothing
    return jax.tree.map(lambda x: 0.1 * x, p)


def _batch():
    return {"d": jnp.zeros((M, 1), jnp.float32)}


def _run(cfg, rounds, grad=_grad, m=M, params=None, batch=None):
    fed = make(cfg)
    s = fed.init(params if params is not None else _params(), m)
    rows = []
    for _ in range(rounds):
        s, mx = fed.round(s, grad, batch if batch is not None else _batch())
        rows.append(mx)
    return fed, s, rows


def _assert_trees_equal(a, b, ignore=("round",)):
    a = {k: v for k, v in a.items() if k not in ignore}
    b = {k: v for k, v in b.items() if k not in ignore}
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_fault_config_parse_round_trips():
    fc = FaultConfig.parse("dropout=0.1,corrupt=0.05,seed=7")
    assert fc.dropout == 0.1 and fc.corrupt == 0.05 and fc.seed == 7
    assert fc.any
    assert not FaultConfig().any
    with pytest.raises(ValueError, match="unknown"):
        FaultConfig.parse("dropuot=0.1")
    with pytest.raises(ValueError):
        FaultConfig(dropout=1.5)


def test_screen_flag_validated():
    with pytest.raises(ValueError, match="screen"):
        FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                        screen="maybe")


def test_cohort_tile_must_divide_cohort():
    # cohort = ceil(0.5 * 8) = 4; tile 3 does not divide it
    with pytest.raises(ValueError) as ei:
        FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                        participation=0.5, num_clients=8, cohort_tile=3)
    assert "3" in str(ei.value) and "4" in str(ei.value)
    # divisors (and tiles >= the cohort, clamped by the engine) stay legal
    FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                    participation=0.5, num_clients=8, cohort_tile=2)
    FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                    participation=0.5, num_clients=8, cohort_tile=4)


# ---------------------------------------------------------------------------
# the fault plan: deterministic, pure, disjoint
# ---------------------------------------------------------------------------

def test_plan_is_deterministic_and_disjoint():
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                          faults=FaultConfig(dropout=0.3, corrupt=0.4, seed=5))
    p1 = faults.plan(cfg, 3, 16)
    p2 = faults.plan(cfg, 3, 16)
    np.testing.assert_array_equal(np.asarray(p1.silent), np.asarray(p2.silent))
    np.testing.assert_array_equal(np.asarray(p1.corrupt), np.asarray(p2.corrupt))
    np.testing.assert_array_equal(np.asarray(p1.kind), np.asarray(p2.kind))
    # a client never both drops AND corrupts: it either returns or it doesn't
    assert not bool(jnp.any(p1.silent & p1.corrupt))
    # different rounds draw different schedules (generically)
    others = [faults.plan(cfg, r, 16) for r in range(8)]
    assert any(not np.array_equal(np.asarray(p1.silent), np.asarray(o.silent))
               for o in others)
    # no schedule -> no plan
    assert faults.plan(FederatedConfig(algorithm="gpdmm", inner_steps=1,
                                       eta=0.1), 0, 4) is None


def test_rate_one_means_everyone():
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                          faults=FaultConfig(dropout=1.0))
    p = faults.plan(cfg, 0, 5)
    assert bool(jnp.all(p.silent))


# ---------------------------------------------------------------------------
# the fused screen kernel: xla vs interpret parity
# ---------------------------------------------------------------------------

def _corrupted_buffer(key, m, w, dtype=jnp.float32):
    u = jax.random.normal(key, (m, w), jnp.float32)
    u = u.at[0].set(jnp.nan).at[1, :1].set(jnp.inf)
    if m > 3:
        u = u.at[3].multiply(1e6)
    return u.astype(dtype)


@pytest.mark.parametrize("shape", [(4, 128), (6, 384), (3, 640), (5, 130)],
                         ids=["one_block", "multi", "wide", "padded_width"])
@pytest.mark.parametrize("per_row", [False, True], ids=["bcast", "per_row"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_screen_kernel_interpret_parity(shape, per_row, dtype):
    m, w = shape
    u = _corrupted_buffer(jax.random.key(0), m, w, dtype)
    ref = jax.random.normal(jax.random.key(1), (m, w) if per_row else (w,),
                            jnp.float32).astype(dtype)
    fin_x, sq_x = ops.screen_uplink(u, ref, impl="xla")
    fin_p, sq_p = ops.screen_uplink(u, ref, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(fin_x), np.asarray(fin_p))
    np.testing.assert_allclose(np.asarray(sq_x), np.asarray(sq_p),
                               rtol=1e-5, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(2, 6), w=st.integers(1, 300), seed=st.integers(0, 2**16),
       per_row=st.booleans())
def test_screen_kernel_parity_property(m, w, seed, per_row):
    k0, k1 = jax.random.split(jax.random.key(seed))
    u = jax.random.normal(k0, (m, w), jnp.float32)
    if seed % 3 == 0:
        u = u.at[seed % m].set(jnp.nan)
    ref = jax.random.normal(k1, (m, w) if per_row else (w,), jnp.float32)
    fin_x, sq_x = ops.screen_uplink(u, ref, impl="xla")
    fin_p, sq_p = ops.screen_uplink(u, ref, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(fin_x), np.asarray(fin_p))
    np.testing.assert_allclose(np.asarray(sq_x), np.asarray(sq_p),
                               rtol=1e-5, atol=1e-4)


def test_screen_tree_matches_packed_arena():
    # the per-leaf tree screen and the packed-arena screen agree on the same
    # state: zero padding contributes zero deviation and a finite flag
    tree = {"a": jax.random.normal(jax.random.key(0), (5, 7)),
            "b": jax.random.normal(jax.random.key(1), (5, 130))}
    ref = {"a": jnp.ones((7,)), "b": 0.5 * jnp.ones((130,))}
    tree["a"] = tree["a"].at[2].set(jnp.nan)
    tree["b"] = tree["b"].at[4].multiply(1e7)
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1,
                          screen=True)
    keep_tree = faults.screen_keep_tree(cfg, tree, ref)
    spec = arena.ArenaSpec.from_tree(ref)
    keep_arena = faults.screen_keep(
        cfg, spec.pack_stacked(tree), spec.pack(ref))
    np.testing.assert_array_equal(np.asarray(keep_tree), np.asarray(keep_arena))
    assert not bool(keep_tree[2]) and not bool(keep_tree[4])


# ---------------------------------------------------------------------------
# the tentpole invariant: screened == participation-masked, bitwise
# ---------------------------------------------------------------------------

def _expected_masks(cfg, rounds, m):
    """The mask a perfect screen produces: active & ~silent & ~corrupt."""
    out = []
    for r in range(rounds):
        p = faults.plan(cfg, r, m)
        out.append(np.asarray(~(p.silent | p.corrupt)))
    return out


@pytest.mark.parametrize("use_arena", [True, False], ids=["arena", "pytree"])
@pytest.mark.parametrize("algo", ALGOS)
def test_screened_round_equals_masked_round(algo, use_arena, monkeypatch):
    rounds = 4
    fc = FaultConfig(dropout=0.25, corrupt=0.3, seed=11)
    cfg_f = FederatedConfig(algorithm=algo, inner_steps=2, eta=0.02,
                            use_arena=use_arena, faults=fc, screen=True)
    _, state_f, rows_f = _run(cfg_f, rounds)

    masks = _expected_masks(cfg_f, rounds, M)
    # identical clients => every corrupted row is demoted, no honest row is
    for r, mx in enumerate(rows_f):
        p = faults.plan(cfg_f, r, M)
        assert float(mx["faults_demoted"]) == float(np.asarray(p.corrupt).sum())
        assert float(mx["faults_injected"]) == float(
            np.asarray(p.silent | p.corrupt).sum())

    # reference: NO faults, NO screen -- just the PR 5 participation-mask
    # path, fed the exact mask the screen produced (rounds run eagerly, one
    # participation_mask call per round)
    it = iter(masks)
    monkeypatch.setattr(T, "participation_mask",
                        lambda key, m, frac: jnp.asarray(next(it)))
    cfg_m = FederatedConfig(algorithm=algo, inner_steps=2, eta=0.02,
                            use_arena=use_arena, participation=0.5,
                            cohort=False)
    _, state_m, _ = _run(cfg_m, rounds)
    _assert_trees_equal(state_f, state_m)


def test_graph_fault_silence_equals_masked(monkeypatch):
    # graph engine: dropout-only faults == stochastic firing with the same
    # per-round mask (screen off isolates the silence path)
    n = 6
    params = {"w": 0.7 * jnp.ones((D,), jnp.float32)}
    batch = {"d": jnp.zeros((n, 1), jnp.float32)}
    rounds = 3
    fc = FaultConfig(dropout=0.4, seed=13)
    cfg_f = FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.02,
                            use_arena=True, topology="ring", faults=fc,
                            screen=False)
    _, state_f, rows_f = _run(cfg_f, rounds, m=n, params=params, batch=batch)
    assert all(math.isfinite(float(mx["faults_injected"])) for mx in rows_f)

    masks = [np.asarray(~faults.plan(cfg_f, r, n).silent)
             for r in range(rounds)]
    it = iter(masks)
    monkeypatch.setattr(T, "participation_mask",
                        lambda key, m, frac: jnp.asarray(next(it)))
    cfg_m = FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.02,
                            use_arena=True, topology="ring",
                            participation=0.5)
    _, state_m, _ = _run(cfg_m, rounds, m=n, params=params, batch=batch)
    _assert_trees_equal(state_f, state_m)


def test_cohort_screened_equals_masked_population():
    # cohort engine with faults+screen vs the masked full-population oracle
    # with the same effective mask: the cohort gather/scatter must preserve
    # the demotion contract row-for-row (cf. tests/test_cohort.py)
    rounds = 3
    fc = FaultConfig(dropout=0.2, corrupt=0.3, seed=17)
    common = dict(algorithm="gpdmm", inner_steps=2, eta=0.02, use_arena=True,
                  participation=0.5, num_clients=M, faults=fc, screen=True)
    cfg_c = FederatedConfig(cohort=True, **common)
    cfg_m = FederatedConfig(cohort=False, **common)
    _, state_c, rows_c = _run(cfg_c, rounds)
    _, state_m, rows_m = _run(cfg_m, rounds)
    _assert_trees_equal(state_c, state_m)
    for mc, mm in zip(rows_c, rows_m):
        assert float(mc["faults_demoted"]) == float(mm["faults_demoted"])


# ---------------------------------------------------------------------------
# the one uplink admission (core.uplink.admit), checked row by row
# ---------------------------------------------------------------------------

# (round, config): each round's plan holds the rows its case is about --
# silent, Inf-corrupted and (async) delayed clients
ADMIT_CASES = {
    "participation": (2, dict(participation=0.5)),
    "screen": (2, dict(faults=FaultConfig(dropout=0.3, corrupt=0.3, seed=5),
                       screen=True, participation=0.5)),
    "async": (4, dict(faults=FaultConfig(delay=0.4, dropout=0.2, corrupt=0.2,
                                         delay_max=2, seed=9),
                      screen=True, max_staleness=2, stale_gamma=0.5)),
}


@pytest.mark.parametrize("case", list(ADMIT_CASES))
@pytest.mark.parametrize("layout", ["arena", "pytree"])
def test_admit_contract(layout, case):
    # silent and demoted rows take the fallback, the screen's median and
    # faults_demoted see only the round's transmitters, and the mask is
    # combine_mask's (fresh rows only under the staleness engine)
    from repro.core import staleness
    from repro.core.uplink import admit, participation_key

    r, kw = ADMIT_CASES[case]
    cfg = FederatedConfig(algorithm="gpdmm", inner_steps=1, eta=0.1, **kw)
    plan = faults.plan(cfg, r, M)
    pmask = (T.participation_mask(participation_key(cfg, r), M,
                                  cfg.participation)
             if cfg.participation < 1.0 else None)
    tx = faults.combine_mask(pmask, plan, None)
    tx = np.ones(M, bool) if tx is None else np.asarray(tx)
    k1, k2 = jax.random.split(jax.random.key(4))
    ref = 0.5 * np.ones(D, np.float32)
    rows = np.array(jax.random.normal(k1, (M, D)))
    # rows that do not transmit sit on the reference: counted in the median,
    # they would pull it to zero and demote every honest transmitter
    rows[~tx] = ref
    cache = np.asarray(jax.random.normal(k2, (M, D)))

    params = {"w": jnp.zeros((D,), jnp.float32)}
    spec = arena.ArenaSpec.from_tree(params)
    if layout == "arena":
        pack = lambda t: np.asarray(spec.pack_stacked({"w": t}))[:, :D]  # noqa: E731
        up, fb = spec.pack_stacked({"w": rows}), spec.pack_stacked({"w": cache})
        adm = admit(cfg, up, arena=spec, round_idx=r, m=M,
                    ref=spec.pack({"w": ref}), fallback=fb,
                    state=staleness.init_arena(spec, M))
    else:
        pack = lambda t: np.asarray(t["w"])  # noqa: E731
        adm = admit(cfg, {"w": jnp.asarray(rows)}, arena=None, round_idx=r,
                    m=M, ref={"w": jnp.asarray(ref)},
                    fallback={"w": jnp.asarray(cache)},
                    state=staleness.init_tree(params, M))
    sent = pack(adm.sent) if layout == "pytree" else np.asarray(adm.sent)[:, :D]
    admitted = (pack(adm.admitted) if layout == "pytree"
                else np.asarray(adm.admitted)[:, :D])

    keep = None
    if faults.screening_on(cfg):
        finite = np.isfinite(sent).all(axis=1)
        d = np.where(np.isfinite(sent), sent - ref, 0.0)
        sq = (d * d).sum(axis=1)
        med = np.median(sq[finite & tx])
        keep = finite & (sq <= cfg.screen_mult * max(med, 1e-12))
    mask = faults.combine_mask(pmask, plan, None if keep is None
                               else jnp.asarray(keep))
    fresh = tx
    if faults.async_on(cfg):
        delayed = np.asarray(plan.delayed)
        assert delayed.any()
        mask = staleness.fresh_mask(mask, plan)
        fresh = tx & ~delayed
    mask = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(adm.mask), mask)
    assert (~mask).any() and mask.any()
    # empty stale slots: no arrival mixes in, so every dropped row is the
    # fallback's and every admitted row is what was sent
    np.testing.assert_array_equal(admitted[mask], sent[mask])
    np.testing.assert_array_equal(admitted[~mask], cache[~mask])
    if keep is None:
        assert adm.metrics == {}
    else:
        assert (~keep & tx).any()
        assert float(adm.metrics["faults_demoted"]) == float(
            (fresh & ~keep).sum())


# ---------------------------------------------------------------------------
# all-silent round: a well-defined no-op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["arena", "pytree", "cohort"])
@pytest.mark.parametrize("algo", ALGOS)
def test_all_silent_round_is_noop(algo, mode):
    kw = dict(algorithm=algo, inner_steps=2, eta=0.02,
              use_arena=mode != "pytree",
              faults=FaultConfig(dropout=1.0, seed=3))
    if mode == "cohort":
        kw.update(participation=0.5, num_clients=4, cohort=True)
    cfg = FederatedConfig(**kw)
    fed = make(cfg)
    s = fed.init({"w": 0.7 * jnp.ones((D,))}, 4)
    b = {"d": jnp.zeros((4, 1), jnp.float32)}
    # one round reaches the all-silent fixed point (x_s -> mean of the
    # cached uplinks); every later round must leave the state bitwise alone
    s, _ = fed.round(s, _grad, b)
    before = jax.tree.map(lambda x: np.asarray(x), s)
    s, mx = fed.round(s, _grad, b)
    _assert_trees_equal(before, s)
    for v in jax.tree.leaves(mx):
        assert bool(jnp.all(jnp.isfinite(jnp.asarray(v, jnp.float32))))


# ---------------------------------------------------------------------------
# same-seed replay: the fault trace is part of the trajectory
# ---------------------------------------------------------------------------

def test_fault_trace_replays_bitwise():
    cfg = FederatedConfig(algorithm="scaffold", inner_steps=2, eta=0.02,
                          use_arena=True,
                          faults=FaultConfig(dropout=0.25, corrupt=0.25,
                                             straggler=0.1, seed=23),
                          screen=True)
    _, s1, r1 = _run(cfg, 5)
    _, s2, r2 = _run(cfg, 5)
    _assert_trees_equal(s1, s2, ignore=())
    for a, b in zip(r1, r2):
        assert float(a["faults_injected"]) == float(b["faults_injected"])
        assert float(a["faults_demoted"]) == float(b["faults_demoted"])


def test_screened_run_tracks_fault_free_run():
    # ISSUE acceptance: with a 10% dropout + 5% corrupt schedule the
    # screened trajectory lands near the fault-free one on a real objective
    prob = quadratic.generate(jax.random.key(0), m=8, n=60, d=D)
    eta = 0.5 / prob.L
    rounds = 40
    base = dict(algorithm="gpdmm", inner_steps=3, eta=eta, use_arena=True)

    def obj(cfg):
        opt = make(cfg)
        s = opt.init(jnp.zeros((prob.d,)), prob.m)
        for _ in range(rounds):
            s, _ = opt.round(s, prob.oracle(), prob.batch())
        return float(prob.F(opt.server_params(s)))

    clean = obj(FederatedConfig(**base))
    faulted = obj(FederatedConfig(
        faults=FaultConfig(dropout=0.1, corrupt=0.05, seed=7), **base))
    # "within 10%" on the scale of the total descent from the zero init
    scale = float(prob.F(jnp.zeros((prob.d,))) - prob.f_star)
    assert math.isfinite(faulted)
    assert abs(faulted - clean) <= 0.1 * scale


# ---------------------------------------------------------------------------
# checkpoint satellite: retention, durability, loud rejection
# ---------------------------------------------------------------------------

def test_checkpoint_keeps_last_n_and_rejects_truncation(tmp_path):
    for s in range(5):
        ckpt.save(tmp_path, s, {"x": jnp.arange(3.0), "s": s}, keep=2)
    names = sorted(p.name for p in pathlib.Path(tmp_path).glob("*.msgpack"))
    assert names == ["step_00000003.msgpack", "step_00000004.msgpack"]
    assert ckpt.latest_step(tmp_path) == 4
    t = ckpt.load(tmp_path)
    assert t["s"] == 4

    fp = tmp_path / "step_00000004.msgpack"
    fp.write_bytes(fp.read_bytes()[:10])
    with pytest.raises(ValueError, match="step_00000004.*truncated or corrupt"):
        ckpt.load(tmp_path, 4)
    with pytest.raises(FileNotFoundError, match="step_00000099"):
        ckpt.load(tmp_path, 99)
