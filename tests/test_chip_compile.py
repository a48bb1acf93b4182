"""Compile-only checks of the Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse -- blocks not aligned to the (8, 128) tiling, kernels past the VMEM
budget -- which interpret-mode tests cannot see.  Each case passes
``impl="pallas"`` and asserts the compiled program holds the Pallas custom
call, at real widths: the packed arena width of ``chip_smoke.py``'s model
(OLMo-1B at its published widths, 4 layers) for the per-client kernels,
OLMo-1B's head dims for attention, and 2^20-wide rows on a ring of 8 nodes
for the graph kernels.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import arena, topology
from repro.kernels import ops
from repro.models import build as build_model

M = 2  # clients in chip_smoke.py's one-chip run
# the femnist.full benchmark cell's client arena: 3,550 writers, 48,670
# softmax parameters packed to 48,768 lanes, f32
ARENA_M, ARENA_W = 3550, 48768
SMOKE_LAYERS = 4  # chip_smoke.py's and the olmo1b.silo2 cell's depth cut
INNER_W = 1024  # the fused K-step kernel keeps (W, W) in VMEM
GRAPH_W = 2 ** 20  # edge-dual rows of a ring of 8 nodes: 16 rows must fit HBM
P = dict(impl="pallas")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)``: a shape on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def width():
    cfg = dataclasses.replace(get_arch("olmo-1b"), n_layers=SMOKE_LAYERS)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    return arena.ArenaSpec.from_tree(shapes).width


def _rows(S, W):
    return S((M, W)), S((W,))


def _fused_update(S, W, per_client):
    cl, row = _rows(S, W)
    if per_client:
        return (lambda x, g, s, lam, e: ops.fused_update_arena(
            x, g, s, lam, e, 1.5, **P), (cl, cl, row, cl, S((M,), jnp.float32)))
    return (lambda x, g, s, lam: ops.fused_update_arena(
        x, g, s, lam, 0.1, 1.5, **P), (cl, cl, row, cl))


def _round_tail(S, W, with_lam_is):
    cl, row = _rows(S, W)
    return (lambda x, lam, s: ops.round_tail(
        x, lam, s, 1.5, with_lam_is=with_lam_is, **P), (cl, cl, row))


def _scaffold_cv(S, W, per_client):
    cl, row = _rows(S, W)
    if per_client:
        return (lambda c, x, cs, s, a: ops.scaffold_cv(c, x, cs, s, a, **P),
                (cl, cl, row, row, S((M,), jnp.float32)))
    return (lambda c, x, cs, s: ops.scaffold_cv(c, x, cs, s, 2.0, **P),
            (cl, cl, row, row))


def _inner_loop(S, per_client):
    f32 = jnp.float32
    cl, row = S((M, INNER_W), f32), S((INNER_W,), f32)
    H = S((M, INNER_W, INNER_W), f32)
    if per_client:  # per-client stepsize and SCAFFOLD's offset row
        return (lambda x, h, c, s, lam, e, off: ops.inner_loop_affine(
            x, h, c, s, lam, e, 1.5, 3, off=off, **P),
            (cl, H, cl, row, cl, S((M,), f32), cl))
    return (lambda x, h, c, s, lam: ops.inner_loop_affine(
        x, h, c, s, lam, 0.1, 1.5, 3, **P), (cl, H, cl, row, cl))


def _graph(S, which):
    t = topology.ring(8)
    z, x = S((t.n_slots, GRAPH_W)), S((t.n, GRAPH_W))
    if which == "neighbor_reduce":
        return (lambda z: ops.neighbor_reduce(
            z, seg=t.src, first=t.first_flags(), sgn=t.sgn, n=t.n, **P), (z,))
    return (lambda z, x: ops.edge_flip(
        z, x, 1.7, rev=t.rev, nbr=t.nbr, sgn=t.sgn, **P), (z, x))


def _flash_attention(S):
    cfg = get_arch("olmo-1b")
    hd = cfg.d_model // cfg.n_heads
    q = S((1, 512, cfg.n_heads, hd))
    kv = S((1, 512, cfg.n_kv_heads, hd))
    pos = S((512,), jnp.int32)
    return (lambda q, k, v, p: ops.flash_attention(q, k, v, p, p, **P),
            (q, kv, kv, pos))


CASES = {
    "fused_update_arena-scalar": lambda S, W: _fused_update(S, W, False),
    "fused_update_arena-per_client": lambda S, W: _fused_update(S, W, True),
    "fused_update_client": lambda S, W: (
        lambda x, g, s, lam, i: ops.fused_update_client(x, g, s, lam, i, 0.1, 1.5, **P),
        (S((M, W)), S((W,)), S((W,)), S((M, W)), S((), jnp.int32))),
    "fused_update_client-f32": lambda S, W: (
        lambda x, g, s, lam, i: ops.fused_update_client(x, g, s, lam, i, 0.1, 1.5, **P),
        (S((M, W), jnp.float32), S((W,), jnp.float32), S((W,), jnp.float32),
         S((M, W), jnp.float32), S((), jnp.int32))),
    "fused_update_client-per_client": lambda S, W: (
        lambda x, g, s, lam, i, st: ops.fused_update_client(x, g, s, lam, i, st, 1.5, **P),
        (S((M, W), jnp.float32), S((W,), jnp.float32), S((W,), jnp.float32),
         S((M, W), jnp.float32), S((), jnp.int32), S((M,), jnp.float32))),
    "fused_update_client-tile": lambda S, W: (
        lambda x, g, s, lam, i: ops.fused_update_client(x, g, s, lam, i, 0.1, 1.5, **P),
        (S((3 * 8, ARENA_W), jnp.float32), S((ARENA_W,), jnp.float32),
         S((ARENA_W,), jnp.float32), S((3 * 8, ARENA_W), jnp.float32), S((), jnp.int32))),
    "round_tail": lambda S, W: _round_tail(S, W, False),
    "round_tail-lam_is": lambda S, W: _round_tail(S, W, True),
    # the olmo1b.silo2 uplink: the running sum of the iterates in, 1/K
    # applied in the kernel, float32 state at m = 2
    "round_tail-scale": lambda S, W: (
        lambda x, lam, s: ops.round_tail(x, lam, s, 1.5, with_lam_is=False,
                                         scale=0.5, **P),
        (S((M, W), jnp.float32), S((M, W), jnp.float32), S((W,), jnp.float32))),
    "dual_from_uplink": lambda S, W: (
        lambda u, s: ops.dual_from_uplink(u, s, 1.5, **P), _rows(S, W)),
    "scaffold_cv-scalar": lambda S, W: _scaffold_cv(S, W, False),
    "scaffold_cv-per_client": lambda S, W: _scaffold_cv(S, W, True),
    "ef21_rowmax_apply": lambda S, W: (
        lambda u, h: ops.ef21_update(u, h, 8, (W // 128,), **P),
        (S((M, W)), S((M, W)))),
    "screen_uplink": lambda S, W: (
        lambda u, s: ops.screen_uplink(u, s, **P), _rows(S, W)),
    "screen_uplink-per_row": lambda S, W: (
        lambda u, r: ops.screen_uplink(u, r, **P), (S((M, W)), S((M, W)))),
    "residual_norm": lambda S, W: (
        lambda x, p: ops.residual_norm(x, p, **P), (S((M, W)), S((M, W)))),
    "stale_mix": lambda S, W: (
        lambda u, c, b, f, st, w: ops.stale_mix(u, c, b, f, st, w, **P),
        (S((M, W)), S((W,)), S((M, W)), S((M,), jnp.bool_),
         S((M,), jnp.bool_), S((M,), jnp.float32))),
    "row_gather": lambda S, W: (
        lambda a, i: ops.row_gather(a, i, **P),
        (S((2 * M, W)), S((M,), jnp.int32))),
    "row_scatter": lambda S, W: (
        lambda d, i, r: ops.row_scatter(d, i, r, **P),
        (S((2 * M, W)), S((M,), jnp.int32), S((M, W)))),
    "inner_loop_affine-scalar": lambda S, W: _inner_loop(S, False),
    "inner_loop_affine-per_client": lambda S, W: _inner_loop(S, True),
    "neighbor_reduce": lambda S, W: _graph(S, "neighbor_reduce"),
    "edge_flip": lambda S, W: _graph(S, "edge_flip"),
    "flash_attention-forward": lambda S, W: _flash_attention(S),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(sds, width, case):
    fn, args = CASES[case](sds, width)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{case}: no Pallas kernel in the compiled program")


def _hlo_shapes(hlo: str) -> dict:
    """Instruction name -> (opcode, dims) of every array-valued instruction."""
    return {n: (op, tuple(int(d) for d in dims.split(",") if d))
            for n, dims, op in re.findall(
                r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", hlo)}


def _operand_ranks(hlo: str, shapes: dict):
    """Ranks of the operands of the one Pallas call in ``hlo``."""
    calls = re.findall(r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 1, calls
    return [len(shapes[a.strip().lstrip("%")][1]) for a in calls[0].split(",")]


ARENA_CASES = {
    # name: (op, donated arguments, (client, server) operands of the call)
    "fused_update_arena": (lambda x, g, s, lam: ops.fused_update_arena(
        x, g, s, lam, 0.1, 1.5, **P), "ccsc", (3, 1)),
    "fused_update_arena-per_client": (lambda x, g, s, lam, e: ops.fused_update_arena(
        x, g, s, lam, e, 1.5, **P), "ccscm", None),
    # the first step carries the running sum out beside x and reads what
    # the plain kernel reads; later steps read the sum too
    "fused_update_arena-sum_first": (lambda x, g, s, lam: ops.fused_update_arena_sum(
        x, g, s, lam, None, 0.1, 1.5, **P), "ccsc", (3, 1)),
    "fused_update_arena_sum": (lambda x, g, s, lam, xsum: ops.fused_update_arena_sum(
        x, g, s, lam, xsum, 0.1, 1.5, **P), "ccscc", (4, 1)),
    "round_tail": (lambda x, lam, s: ops.round_tail(
        x, lam, s, 1.5, with_lam_is=False, **P)[1], "ccs", (2, 1)),
    "round_tail-scale": (lambda x, lam, s: ops.round_tail(
        x, lam, s, 1.5, with_lam_is=False, scale=0.2, **P)[1], "ccs", (2, 1)),
    "round_tail-lam_is": (lambda x, lam, s: ops.round_tail(
        x, lam, s, 1.5, **P), "ccs", None),
    "dual_from_uplink": (lambda u, s: ops.dual_from_uplink(u, s, 1.5, **P), "cs", None),
    "scaffold_cv-per_client": (lambda c, x, cs, s, a: ops.scaffold_cv(
        c, x, cs, s, a, **P), "ccssm", None),
}


@pytest.mark.parametrize("case", sorted(ARENA_CASES))
def test_arena_kernels_read_the_arena_in_place(sds, case):
    """At the femnist.full cell's shape the elementwise arena kernels read
    the (m, width) buffers as they lie: the compiled program holds no pad
    and no copy of the client arena's size (the eq. (20) kernel writes its
    donated x in place), and the Pallas call reads rank-3 ``(1, m, width)``
    client operands beside rank-2 ``(1, width)`` server rows -- the operand
    signature by which the benchmark's roofline readers know a kernel."""
    fn, kinds, signature = ARENA_CASES[case]
    shape = {"c": ((ARENA_M, ARENA_W), jnp.float32), "s": ((ARENA_W,), jnp.float32),
             "m": ((ARENA_M,), jnp.float32)}
    args = [sds(*shape[k]) for k in kinds]
    # the eq. (20) kernels write over x, and the summing one over the sum,
    # which the inner loop donates
    donate = ((0, 4) if case == "fused_update_arena_sum"
              else 0 if case.startswith("fused_update_arena") else ())
    hlo = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    shapes = _hlo_shapes(hlo)
    big = [(n, op, dims) for n, (op, dims) in shapes.items()
           if op in ("pad", "copy") and math.prod(dims) >= ARENA_M * ARENA_W]
    assert not big, big
    ranks = _operand_ranks(hlo, shapes)
    assert ranks[:kinds.index("s")] == [3] * kinds.index("s"), ranks
    if signature is not None:
        assert (ranks.count(3), ranks.count(2)) == signature, ranks
    assert ops.LAYOUT[case.split("-")[0]][0] % 8 == 0


@pytest.fixture(scope="module")
def silo_round(topo):
    """The olmo1b.silo2 round compiled for one v5e: OLMo-1B at its
    published widths and 4 layers, float32 state and bfloat16 compute, two
    silos, one 2048-token sequence each.  Returns the compiled round, the
    arena width and what the round kernels resolved to."""
    from repro.configs.base import FederatedConfig
    from repro.core import make

    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    model = build_model(dataclasses.replace(get_arch("olmo-1b"), n_layers=SMOKE_LAYERS))
    fed = make(FederatedConfig(algorithm="gpdmm", inner_steps=2, eta=0.05,
                               num_clients=M, layout="client_axis"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    state = on_chip(jax.eval_shape(lambda p: fed.init(p, M), params))
    toks = jax.ShapeDtypeStruct((M, 1, 2048), jnp.int32, sharding=one_chip)

    def one_round(s, b):
        return fed.round(s, lambda p, bi: jax.grad(lambda q: model.loss(q, bi)[0])(p), b)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "default_impl", lambda: "pallas")
        compiled = jax.jit(one_round, donate_argnums=0).lower(
            state, {"tokens": toks, "targets": toks}).compile()
        resolved = dict(ops.RESOLVED)
    width = arena.ArenaSpec.from_tree(params).width
    assert state["x_c"].shape == (M, width) and state["x_c"].dtype == jnp.float32
    return compiled, width, resolved


def test_silo_round_fits_one_chip(silo_round):
    """The olmo1b.silo2 round compiles for one v5e.  Its five float32 arena
    rows (x_s, two x_c, two lam) leave the round about 8 GB, which holds
    because a plain gradient steps the arena a client at a time and the
    kernels read the arena in place: no copy or pad of the client arena's
    size."""
    compiled, width, resolved = silo_round
    big = [(n, op, dims) for n, (op, dims) in _hlo_shapes(compiled.as_text()).items()
           if op in ("pad", "copy") and math.prod(dims) >= M * width]
    assert not big, big
    assert resolved["fused_update_client"] == "pallas"


def _batch_dims(spec: str) -> str:
    """The batch dims of an einsum ``spec``: letters of both operands and
    the output."""
    ins, out = spec.split("->")
    lhs, rhs = ins.split(",")
    return "".join(c for c in out if c in lhs and c in rhs)


def test_silo_round_recomputes_no_weight_matmul(silo_round):
    """The backward pass of the olmo1b.silo2 round reruns none of the
    model's weight matmuls: each checkpointed layer keeps their outputs, so
    the only matmuls the compiled program recomputes (under JAX's
    ``rematted_computation`` name) are attention's score and value
    products, which have batch dims.  An einsum is known by the spec in its
    name; a plain ``@`` names none and counts as a weight matmul."""
    compiled = silo_round[0]
    rematted = [name.split("/")[-2] for name in re.findall(
        r"= \S+ (?:convolution|dot)\(.*op_name=\"([^\"]*)\"", compiled.as_text())
        if "/rematted_computation/" in name]
    assert rematted, "no rematerialised matmul: attention's products should be"
    weight = [n for n in rematted if "->" not in n or not _batch_dims(n)]
    assert not weight, weight


def test_platform_selects_the_implementation(monkeypatch):
    """``impl=None`` resolves from the platform: Pallas on a TPU, XLA
    elsewhere -- except attention and wkv6, which have no Pallas backward
    and stay on XLA inside the training step."""
    x = jnp.zeros((M, 256))
    s = jnp.zeros((256,))
    for backend, want in (("tpu", "pallas"), ("cpu", "xla")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.default_impl() == want
        jax.eval_shape(lambda u, r: ops.dual_from_uplink(u, r, 1.0), x, s)
        assert ops.RESOLVED["dual_from_uplink"] == want
    q = jnp.zeros((1, 128, 2, 64))
    pos = jnp.arange(128)
    jax.eval_shape(lambda q: ops.flash_attention(q, q, q, pos, pos), q)
    assert ops.RESOLVED["flash_attention"] == "xla"
