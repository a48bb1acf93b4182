"""Chip smoke run: federated GPDMM training of OLMo-1B on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips (2x2 v5e host)

One chip: ``repro.launch.train.run`` trains OLMo-1B at its published widths
(d_model 2048, 16 heads, d_ff 8192, vocab 50304), cut only in depth, with
GPDMM over m = 2 clients for a few rounds, at OLMo's training precision
(float32 weights and client state, bfloat16 compute).  It then checks, on
the chip, that every logged server loss is finite, that the eq. (25) KKT
invariant ``lam_sum_norm`` sits at rounding level, and that the round
kernels the run took (``fused_update_client``, ``round_tail``,
``dual_from_uplink``) give the same result as Pallas kernels and as the XLA
reference on the run's own state.

Four chips: one GPDMM round at m = 4 with the client dim sharded over a
4-chip ``data`` mesh (``launch/steps.build_train_step``), against the same
round from the same state and batch on one chip.

Times printed here are a smoke reading of one run, not a benchmark.  The
script exits non-zero, printing no result, when JAX finds no TPU; every
failed check raises.  The last line of a passing run is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "olmo-1b"
# Depth is the only cut, to the deepest that fits.  With float32 client
# state a GPDMM round over m = 2 clients holds five arena rows of 1.49 GB
# at 4 layers as arguments, and the v5e compiler fits its temporaries
# beside them; at 5 layers it is refused.  With m = 4 (the four-chip
# phase's one-chip reference) only 1 layer fits: 6.12 GB of arguments and
# 6.81 GB of temporaries; 2 layers are refused by 0.17 GB.
LAYERS = 4
M = 2
FOUR_CHIP_LAYERS = 1
FOUR_CHIP_M = 4
K = 2
SEQ = 128
BATCH = 4  # sequences per client per round
ROUNDS = 4
ETA = 0.05  # 0.3 (the launcher default) grew the loss 13 -> 496 in 4 rounds
SEED = 0

BF16_ULP = 2.0 ** -7  # bf16 spacing relative to the value (8-bit significand);
# the tolerances below allow one of it, which holds for float32 state too


def require_tpu(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, JAX found {len(devs)}")
    return devs


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def gb(n_bytes) -> str:
    return f"{n_bytes / 1e9:.3f} GB"


@jax.jit
def agreement(a, b, floor):
    """Element-wise bf16 agreement of two buffers: (max |a - b|, max excess
    over the tolerance ``BF16_ULP * max(|a|, |b|) + floor``, fraction of
    bitwise-equal elements).  The tolerance is one bf16 ulp of the larger
    value; ``floor`` covers results near zero after cancellation."""
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    d = jnp.abs(a32 - b32)
    tol = BF16_ULP * jnp.maximum(jnp.abs(a32), jnp.abs(b32)) + floor
    return jnp.max(d), jnp.max(d - tol), jnp.mean((a == b).astype(jnp.float32))


@jax.jit
def absmax(a):
    return jnp.max(jnp.abs(a.astype(jnp.float32)))


@jax.jit
def kkt_bound(x_s, lam, rho):
    """Where bf16 rounding can put lam_sum_norm = ||sum_i lam_i|| (zero in
    exact arithmetic, eq. 25): each lam_i = rho (u_i - x_s) rounds by half
    an ulp and so does the bf16 mean x_s, so the sum can reach
    2^-9 (rho m ||x_s|| + sum_i ||lam_i||).  Returns twice that."""
    f32 = jnp.float32
    xs = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(f32)))
                      for v in jax.tree.leaves(x_s)))
    lam_rows = jnp.linalg.norm(lam.astype(f32), axis=-1)
    return 2.0 ** -8 * (rho * lam.shape[0] * xs + jnp.sum(lam_rows))


def assert_agree(name: str, a, b, floor) -> None:
    dmax, excess, same = (float(v) for v in agreement(a, b, floor))
    print(f"[smoke] {name}: max|diff| {dmax:.3e}, bitwise-equal "
          f"{same:.6f}, tolerance 1 bf16 ulp + {float(floor):.3e}", flush=True)
    check(excess <= 0.0, f"{name}: differs by more than the tolerance "
                         f"(excess {excess:.3e})")


def smoke_cfg(layers: int):
    from repro.configs import get_arch

    cfg = get_arch(ARCH)
    return dataclasses.replace(cfg, n_layers=layers)


def round_times(trace_path: str):
    """Per-round wall seconds (dispatch + block_until_ready spans) from the
    launcher's span trace; the first round includes the compile."""
    from repro.telemetry import load_trace

    evs = load_trace(trace_path)
    disp = [e["dur"] for e in evs if e.get("name") == "round/dispatch"]
    wait = [e["dur"] for e in evs if e.get("name") == "round/block_until_ready"]
    check(len(disp) == len(wait) == ROUNDS,
          f"trace holds {len(disp)}/{len(wait)} round spans, want {ROUNDS}")
    return [(d + w) / 1e6 for d, w in zip(disp, wait)]


def one_chip(dev) -> None:
    from repro.configs import get_arch
    from repro.core import arena
    from repro.core.api import resolved_rho
    from repro.data.synthetic import lm_batches
    from repro.kernels import ops
    from repro.launch import train
    from repro.models import build as build_model

    cfg = smoke_cfg(LAYERS)
    model = build_model(cfg)
    spec = arena.ArenaSpec.from_tree(
        jax.eval_shape(model.init, jax.random.key(SEED)))
    print(f"[smoke] {ARCH}: d_model {cfg.d_model}, heads {cfg.n_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, layers {cfg.n_layers} "
          f"of {get_arch(ARCH).n_layers}, m {M}, K {K}, seq {SEQ}, "
          f"batch {BATCH}/client", flush=True)
    print(f"[smoke] packed arena width {spec.width} ({spec.dtype}); "
          f"{gb(spec.width * jnp.dtype(spec.dtype).itemsize)} per client copy",
          flush=True)

    with tempfile.TemporaryDirectory() as tmp:
        trace = str(pathlib.Path(tmp) / "trace.json")
        t0 = time.perf_counter()
        history = train.run(
            ARCH, reduced=False, layers=LAYERS, steps=ROUNDS,
            algorithm="gpdmm", k=K, eta=ETA, m=M, per_client_batch=BATCH,
            seq_len=SEQ, seed=SEED, log_every=1, trace_out=trace)
        wall = time.perf_counter() - t0
        secs = round_times(trace)
    used = dict(ops.RESOLVED)
    steady = float(np.median(secs[1:]))
    print(f"[smoke] implementations: {json.dumps(used, sort_keys=True)}",
          flush=True)
    print(f"[smoke] arena kernel layouts: "
          f"{json.dumps(ops.LAYOUT, sort_keys=True)}", flush=True)
    print(f"[smoke] smoke reading, not a benchmark: run {wall:.1f} s; first "
          f"round (compile + run) {secs[0]:.2f} s; later rounds "
          f"{', '.join(f'{s:.3f}' for s in secs[1:])} s; compile "
          f"~{secs[0] - steady:.1f} s", flush=True)
    print(f"[smoke] peak_bytes_in_use {gb(dev.memory_stats()['peak_bytes_in_use'])}",
          flush=True)

    for op in ("fused_update_client", "round_tail", "dual_from_uplink"):
        check(used.get(op) == "pallas", f"{op} ran {used.get(op)}, not pallas")
        # M clients are fewer than a sublane tile: one block holds all M rows
        check(ops.LAYOUT[op][0] == M, f"{op} took blocks {ops.LAYOUT[op]}")
    losses = [row["server_loss"] for row in history]
    check(len(losses) == ROUNDS and all(np.isfinite(losses)),
          f"server losses {losses}")

    state = history.state
    history.state = None
    rho = resolved_rho(dataclasses.replace(cfg.fed, inner_steps=K, eta=ETA))
    bound = float(kkt_bound(state["x_s"], state["lam_s"], rho))
    kkt = history[-1]["lam_sum_norm"]
    print(f"[smoke] server_loss {losses}; lam_sum_norm {kkt:.4e} "
          f"(bf16 rounding bound {bound:.4e})", flush=True)
    check(kkt <= bound, f"lam_sum_norm {kkt} above its bf16 bound {bound}")

    # The round kernels on the run's own buffers, Pallas against XLA.  Each
    # arena row is 1.49 GB, so the checks run in an order that frees what
    # the next does not read: eq. (20) on client 0 (its Pallas call writes
    # x in place), the uplink from that result, then the dual refresh.
    x_s = jax.jit(spec.pack)(state.pop("x_s"))
    x, lam = state.pop("x_c"), state.pop("lam_s")
    batch = next(lm_batches(jax.random.key(SEED + 1), 1, M, BATCH, SEQ,
                            cfg.vocab_size))
    g = jax.jit(lambda x, b: spec.pack(jax.grad(lambda q: model.loss(q, b)[0])(
        spec.unpack(x[0]))))(x, jax.tree.map(lambda t: t[0], batch))
    step = 1.0 / (1.0 / ETA + rho)

    def update(impl, donate=()):
        return jax.jit(lambda x, g, s, lam: ops.fused_update_client(
            x, g, s, lam, jnp.int32(0), step, rho, impl=impl), donate_argnums=donate)

    b = update("xla")(x, g, x_s, lam)
    a = update("pallas", 0)(x, g, x_s, lam)
    del x, g
    assert_agree("fused_update_client", a, b, 2.0 ** -15 * absmax(b))
    del a
    a, uplink = (jax.jit(lambda x, s, lam, impl=impl: ops.round_tail(
        x, lam, s, rho, with_lam_is=False, impl=impl)[1])(b, x_s, lam)
        for impl in ("pallas", "xla"))
    del b, lam
    assert_agree("round_tail", a, uplink, 2.0 ** -15 * absmax(uplink))
    del a
    a, b = (jax.jit(lambda u, s, impl=impl: ops.dual_from_uplink(
        u, s, rho, impl=impl))(uplink, x_s) for impl in ("pallas", "xla"))
    # rho (u - x_s) cancels: its floor is rho times one ulp of the inputs
    assert_agree("dual_from_uplink", a, b, rho * 2.0 ** -15 * absmax(uplink))
    print(f"[smoke] peak_bytes_in_use after the kernel check "
          f"{gb(dev.memory_stats()['peak_bytes_in_use'])}", flush=True)


def four_chips(devs) -> None:
    from repro.configs.base import ShapeConfig
    from repro.core import make as make_fed
    from repro.core.api import resolved_rho
    from repro.data.synthetic import lm_batches
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.steps import build_train_step
    from repro.models import build as build_model

    cfg = smoke_cfg(FOUR_CHIP_LAYERS)
    cfg = dataclasses.replace(cfg, fed=dataclasses.replace(
        cfg.fed, algorithm="gpdmm", inner_steps=K, eta=ETA,
        num_clients=FOUR_CHIP_M, layout="client_axis"))
    mesh = make_smoke_mesh(FOUR_CHIP_M, 1)
    shape = ShapeConfig("chip_smoke", SEQ, FOUR_CHIP_M * BATCH, "train")
    bundle = build_train_step(cfg, shape, mesh)
    check(bundle.meta["m"] == FOUR_CHIP_M, f"mesh gives m={bundle.meta['m']}")
    print(f"[smoke] four chips: {ARCH} d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}, layers {cfg.n_layers}, m {FOUR_CHIP_M} sharded "
          f"over mesh {dict(mesh.shape)}", flush=True)

    model, fed = build_model(cfg), make_fed(cfg.fed)
    state = jax.jit(lambda k: fed.init(model.init(k), FOUR_CHIP_M))(
        jax.random.key(SEED))
    batch = next(lm_batches(jax.random.key(SEED + 1), 1, FOUR_CHIP_M, BATCH,
                            SEQ, cfg.vocab_size))
    host_state, host_batch = jax.device_get((state, batch))

    # reference: the same round on one chip (no mesh: plain kernels)
    t0 = time.perf_counter()
    ref, ref_metrics = jax.jit(bundle.fn, donate_argnums=(0,))(state, batch)
    ref_state, ref_metrics = jax.device_get((ref, ref_metrics))
    del state, ref
    t1 = time.perf_counter()
    st_shard, b_shard = bundle.in_shardings
    with jax.set_mesh(mesh):
        out, metrics = jax.jit(
            bundle.fn, in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        )(jax.device_put(host_state, st_shard),
          jax.device_put(host_batch, b_shard))
        jax.block_until_ready(out)
    t2 = time.perf_counter()
    print(f"[smoke] smoke reading, not a benchmark: one-chip round (compile "
          f"+ run) {t1 - t0:.1f} s; sharded round (compile + run) "
          f"{t2 - t1:.1f} s", flush=True)
    check(out["lam_s"].sharding.spec[0] is not None,
          f"lam_s is not client-sharded: {out['lam_s'].sharding}")

    # Each program rounds the client rows on its own (the two compile the
    # per-client gradient differently), so an uplink row can move by one
    # bf16 ulp at the rows' scale S, and the server mean x_s with it: allow
    # two ulps at S, times rho for lam_s = rho (u - x_s).
    rho = resolved_rho(cfg.fed)
    ref_dev = jax.device_put(ref_state, st_shard)
    check(set(out) == {"x_s", "x_c", "lam_s", "round"}, f"state {sorted(out)}")
    check(int(out["round"]) == int(ref_state["round"]), "round counters differ")
    floor = 2.0 ** -6 * absmax(ref_dev["x_c"])
    for i, (a, b) in enumerate(zip(jax.tree.leaves(out["x_s"]),
                                   jax.tree.leaves(ref_dev["x_s"]))):
        assert_agree(f"sharded vs one-chip x_s[{i}]", a, b, floor)
    assert_agree("sharded vs one-chip x_c", out["x_c"], ref_dev["x_c"], floor)
    assert_agree("sharded vs one-chip lam_s", out["lam_s"], ref_dev["lam_s"],
                 rho * floor)
    for key in sorted(ref_metrics):
        a, b = float(metrics[key]), float(ref_metrics[key])
        print(f"[smoke] metric {key}: sharded {a:.6e}, one chip {b:.6e}",
              flush=True)
        if key == "lam_sum_norm":  # rounding level on both sides
            bound = float(kkt_bound(out["x_s"], out["lam_s"], rho))
            check(a <= bound, f"sharded lam_sum_norm {a} above {bound}")
        else:
            check(np.isfinite(a) and abs(a - b) <= 1e-2 * abs(b) + 1e-6,
                  f"metric {key}: {a} vs {b}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the training run and kernel check; 4: the "
                         "client-sharded round against the one-chip round")
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    from repro.launch import compile_cache

    print(f"[smoke] compile cache: {compile_cache.enable()}", flush=True)
    if args.chips == 4:
        four_chips(devs[:4])
    else:
        one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
