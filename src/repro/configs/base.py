"""Configuration dataclasses for architectures, input shapes and federated runs.

Every assigned architecture gets one ``ArchConfig`` (see ``src/repro/configs/<id>.py``)
with the exact published hyper-parameters, plus a ``reduced()`` variant used by the
CPU smoke tests (2 layers, d_model <= 512, <= 4 experts).

The federated-optimisation technique of the paper (GPDMM / AGPDMM, Zhang et al. 2021)
is configured via ``FederatedConfig`` and applies to *training* only; decode shapes
exercise the serving path, which is pure substrate.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Input shapes (assigned, public pool)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input shapes."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Federated (paper technique) configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaultConfig:
    """Deterministic client-fault schedule (the robustness layer, ISSUE 6).

    Every fault is a pure function of ``(seed, round, client)`` --
    ``core.faults.plan`` folds the round counter into ``seed`` -- so a fault
    trace replays EXACTLY across reruns, resumes, and watchdog rollbacks.

    Two hard silence classes -- ``dropout`` (the client crashed) and
    ``straggler`` (missed the round barrier) -- map onto the u_hat silence
    contract: the server reuses its cached uplink for the round, exactly as
    for a participation-masked client.  ``delay`` is the SOFT class: with
    the bounded-staleness engine on (``core.faults.async_on``) a delayed
    client's uplink lands ``s in [1, delay_max]`` rounds late through the
    stale buffer (``core.staleness``); with the engine off -- the default,
    and always on non-star topologies -- ``delay`` degrades to silence,
    bit-identical to the pre-async behaviour.  ``corrupt`` clients DO
    transmit, but the wire mangles the packet (NaN row / Inf row / sign
    flip / ``blowup`` x magnitude; the class is drawn per client) -- the
    faults uplink screening (``FederatedConfig.screen``) exists to catch.
    """

    dropout: float = 0.0    # P(client never returns this round)
    straggler: float = 0.0  # P(client misses the round barrier)
    delay: float = 0.0      # P(uplink delayed s rounds; silence if async off)
    corrupt: float = 0.0    # P(transmitted uplink mangled on the wire)
    blowup: float = 1e6     # magnitude multiplier of the "blowup" corruption
    seed: int = 1234        # fault RNG seed, independent of the data/mask seeds
    delay_max: int = 4      # lateness s drawn uniformly from [1, delay_max]

    def __post_init__(self):
        for name in ("dropout", "straggler", "delay", "corrupt"):
            v = getattr(self, name)
            if not (0.0 <= v <= 1.0):
                raise ValueError(
                    f"fault rate {name} must be in [0, 1], got {v}")
        if self.delay_max < 1:
            raise ValueError(
                f"delay_max must be a positive lateness bound, got "
                f"{self.delay_max}")

    @property
    def any(self) -> bool:
        return (self.dropout > 0 or self.straggler > 0 or self.delay > 0
                or self.corrupt > 0)

    @classmethod
    def parse(cls, spec: str) -> "FaultConfig":
        """Build from a CLI spec string, e.g. ``"dropout=0.1,corrupt=0.05,seed=7"``."""
        kwargs = {}
        for item in spec.split(","):
            item = item.strip()
            if not item:
                continue
            key, _, val = item.partition("=")
            key = key.strip()
            if key not in cls.__dataclass_fields__:
                raise ValueError(
                    f"unknown fault field {key!r} (have "
                    f"{sorted(cls.__dataclass_fields__)})")
            kwargs[key] = int(val) if key in ("seed", "delay_max") else float(val)
        return cls(**kwargs)


@dataclass(frozen=True)
class FederatedConfig:
    """How the paper's centralised-network optimisers map onto the mesh.

    ``layout`` selects the memory layout of the per-client state:

    * ``"client_axis"`` -- one client per slice of the client mesh axis
      (``data`` on the single-pod mesh, ``("pod", "data")`` multi-pod).  The
      stacked client state has leading dim ``m`` sharded over that axis.  This
      is the faithful mapping of the server-client star graph: the server
      update is an all-reduce over the client axis.
    * ``"fsdp"`` -- small ``m`` with the per-client copies replicated along the
      client dim but fully-sharded (FSDP) over ``data`` x ``model`` in the
      parameter dims.  Required for the very large models (llama4-maverick,
      yi-34b) where ``m`` full dual copies would not fit HBM.
    """

    algorithm: str = "gpdmm"  # gpdmm | agpdmm | scaffold | fedavg | fedsplit
    inner_steps: int = 2  # K in the paper
    # Gradient stepsize (eta in Alg. 1/2).  Three forms:
    #   * float          -- one global stepsize, the paper's setting;
    #   * "auto"         -- derive PER-CLIENT stepsizes eta_i = safety / L_i
    #                       from a power-iteration / Hutchinson estimate of
    #                       each client's smoothness L_i (core.autotune).
    #                       MUST be resolved host-side before the round is
    #                       built: ``core.autotune.resolve`` replaces it with
    #                       the tuple form below; ``core.make`` rejects an
    #                       unresolved "auto" loudly.
    #   * tuple[float]   -- resolved per-client stepsizes, one per client row
    #                       (hashable, so the config stays jit-static; the
    #                       kernels take the derived values as a per-client
    #                       stepsize operand instead of a baked scalar).
    eta: float | str | Tuple[float, ...] = 1e-2
    rho: Optional[float] = None  # None -> 1/(K*eta), the paper's default
    #                              (mean eta under per-client auto-eta; see
    #                              core.api.resolved_rho)
    layout: str = "client_axis"
    num_clients: Optional[int] = None  # None -> client axis size
    # algorithm variants
    use_avg: bool = True  # GPDMM dual update: eq (23) x-bar (True, Alg. 1)
    #                       vs eq (24) last iterate (False, Remark 1)
    fedsplit_init: str = "z"  # Inexact FedSplit client init: "z" (faithful,
    #                           the improper init the paper diagnoses) | "xs"
    gamma: Optional[float] = None  # FedSplit prox weight; None -> 1/rho
    eta_g: float = 1.0  # SCAFFOLD server stepsize
    # beyond-paper (SSPerf H3): quantise the client uplink to int<bits> with
    # error feedback before the server mean.  None = exact (paper-faithful).
    # Extends the paper's 1-variable-per-direction claim from 16 to <bits>
    # bits/param on the wire; the SPMD dry-run keeps the bf16 collective (XLA
    # has no sub-byte all-reduce) -- the saving applies to the real
    # server-client deployment and is reported analytically.
    uplink_bits: Optional[int] = None
    # beyond-paper: partial client participation (async PDMM, cf. paper
    # SSIII-A's asynchronous updating).  Each round exactly ceil(frac*m)
    # clients run the K inner steps and transmit; the server reuses its cached
    # view u_hat_i of every silent client, recomputing lam_{s|i} = rho(u_i -
    # x_s) for ALL i from what it holds -- so the KKT invariant (25) survives
    # partial rounds exactly.  1.0 = every client every round (paper-faithful).
    participation: float = 1.0
    # Cohort-sampled round engine (ISSUE 5).  With ``participation < 1`` the
    # masked path still runs the K-step inner loop over ALL m client rows and
    # only discards silent clients at the tail, so compute is O(m) even when
    # 1% of clients fire.  The cohort engine instead GATHERS the round's
    # active rows out of the population arena, runs the fused inner loop and
    # round tail on the (m_active, width) cohort buffer, and SCATTERS the
    # updated rows back -- the server mean becomes
    # (sum_active uplink + sum_silent u_hat) / m, computed as one mean over
    # the scattered population buffer so it matches the masked path
    # row-for-row (tests/test_cohort.py).  "auto" (default) engages whenever
    # the round runs on the arena with participation < 1 and the cohort is
    # strictly smaller than the population; True forces it (when the arena
    # path is taken), False keeps the masked full-population path.  The
    # engine is arena-only: the pytree path always masks.
    cohort: bool | str = "auto"
    # Runs the cohort inner loop in fixed-size tiles via ``lax.map`` so peak
    # live inner-loop state (notably the (tile, W, W) affine H blocks and the
    # per-step gradient temporaries) is O(tile) instead of O(m_active) --
    # what makes ~10^5-10^6-row population arenas with small cohorts feasible
    # on one host.  Must divide the cohort size; None = one shot.
    cohort_tile: Optional[int] = None
    # Host-resident population store (core.popstore, ISSUE 8): keep every
    # resident (m, width) client-state buffer in HOST memory as numpy arrays,
    # stage only the sampled cohort's rows onto device each round (with the
    # next round's gather prefetched while the current round computes), and
    # scatter the updated rows back after the tail -- device memory becomes
    # O(cohort) while the population scales to 10^6 rows.  Server-side O(m)
    # reads are O(cohort) too: the running sum(u_hat) is maintained
    # incrementally (compensated f64) and the dense dual refresh is
    # represented lazily as lam_i = rho*(u_hat_i - x_s).  Requires the
    # cohort engine (arena path, participation < 1, star, no async);
    # "auto" engages when the cohort engine runs and the population is at
    # least ``popstore_min_clients``; True forces it whenever the cohort
    # engine runs; False keeps the device-resident arena.  A popstore round
    # equals the device-arena cohort round row-for-row at f32 on the same
    # participation draw (tests/test_popstore.py).
    popstore: bool | str = "auto"
    # Population size at which "auto" moves the resident state off device.
    # Below this the O(m) device buffers are cheap and the device-arena
    # cohort round avoids per-round host<->device staging.
    popstore_min_clients: int = 65_536
    # Seed for the participation RNG (folded with the round counter).  One
    # config field instead of a constant duplicated per algorithm, so two
    # algorithms under comparison draw IDENTICAL mask sequences by contract
    # when given the same seed.
    seed: int = 17
    # Run the round's elementwise hot path over the flat client-state arena
    # (core.arena): all leaves of a client packed into one contiguous
    # 128-lane-padded row, so the K inner steps and the round tail are a
    # handful of fused whole-buffer kernels instead of per-leaf tree.map
    # chains.  ALL five algorithms dispatch on this flag (GPDMM/AGPDMM/
    # FedSplit since ISSUE 1-2; SCAFFOLD/FedAvg since ISSUE 3, so the
    # paper's cross-algorithm benchmarks compare algorithms, not
    # implementations).  Numerically equivalent (same f32 math, checked in
    # tests/test_arena.py + tests/test_conformance.py); automatically falls
    # back to the pytree path for
    # layout="fsdp" (per-leaf parameter shardings must be preserved) and for
    # mixed-dtype trees (one buffer would promote all client state to the
    # widest leaf dtype).
    #
    # "auto" (the default) additionally falls back when the packed width is
    # below ``arena_min_width`` -- BENCH_round.json shows the pytree path
    # winning at the paper's tiny shapes, where per-round pack/dispatch
    # overhead swamps the fused-kernel savings.  True forces the arena,
    # False forces the pytree path; every round records the decision in its
    # metrics (``used_arena``).
    use_arena: bool | str = "auto"
    arena_min_width: int = 1024
    # Rounds executed inside ONE jitted call: the launcher wraps
    # ``fed.round`` in a ``lax.scan`` over a leading R dim of the batch
    # stream with the state donated in place (metrics come back stacked),
    # amortising the per-round dispatch overhead that dominates wall time at
    # small state sizes.  1 = one dispatch per round (previous behaviour).
    rounds_per_call: int = 1
    # Network topology of the consensus graph.  "star" (the paper's
    # centralised network, the default) keeps every algorithm on its
    # centralised fast path; any other value routes PDMM/GPDMM through the
    # decentralized graph subsystem (``core.pdmm_graph`` over
    # ``core.topology``: node-primal + edge-dual arenas, neighbor-reduce
    # kernels).  Accepted: "star" | "ring" | "complete" | "torus" |
    # "er"/"er:<p>" (Erdos-Renyi, made connected, drawn from ``seed``).
    # Algorithms without a decentralized analogue (scaffold / fedavg /
    # agpdmm / fedsplit) reject non-star topologies loudly in ``core.make``.
    topology: str = "star"
    # Firing schedule of the graph rounds: "color" fires the greedy color
    # classes sequentially within a round (on a star: clients then server --
    # exactly the centralised algorithm, the conformance contract in
    # tests/test_topology.py); "sync" fires every node at once from the
    # round-start duals (Jacobi PDMM).  Stochastic node firing rides
    # ``participation`` < 1 on the shared ``seed`` mask contract.
    graph_schedule: str = "color"
    # beyond-paper: SVRG-style variance reduction for the stochastic setting
    # the paper names as future work (SSVII), following [14]'s PDMM+SVRG for
    # P2P.  "svrg" corrects each per-step minibatch gradient with the
    # snapshot gradient at the round's server estimate.  None = plain
    # stochastic gradients (paper-faithful).
    variance_reduction: Optional[str] = None
    # Deterministic fault injection (core.faults).  None = fault-free rounds
    # (the default, bit-identical to pre-robustness behaviour).
    faults: Optional[FaultConfig] = None
    # Fused uplink screening (kernels/screen.py via ops.screen_uplink): ONE
    # pass over the (m, width) uplink buffer emits per-client finite flags
    # and squared deviations from the downlink reference; the server demotes
    # any non-finite or norm-outlier client to SILENT for the round (its
    # cached u_hat uplink is reused), so a screened round is bit-identical
    # to a participation-masked round.  "auto" screens exactly when a fault
    # schedule is configured; True always screens (also catches NaNs the
    # optimiser itself produces); False never screens -- a corrupted uplink
    # then poisons the server mean (the failure mode docs/robustness.md
    # demonstrates).
    screen: bool | str = "auto"
    # Norm-outlier rule: demote clients whose squared deviation from the
    # reference exceeds screen_mult x the round median.  <= 0 disables the
    # outlier rule (non-finite screening still applies).
    screen_mult: float = 100.0
    # Bounded-staleness async round engine (core.staleness, ISSUE 7): give
    # the ``delay`` fault class real semantics -- a delayed client's uplink
    # lands s rounds late through a stale-buffer arena and is admitted into
    # the server mean with a staleness-discounted weight gamma**s iff
    # s <= max_staleness, the stale-update regime asynchronous PDMM
    # converges under (Sherson et al., arXiv:1706.02654; Zhang & Heusdens,
    # arXiv:1702.00841).  "auto" (default) engages exactly when the knobs
    # deviate from the synchronous point (max_staleness > 0 or a finite
    # deadline) AND a delay schedule is active on a star topology; True
    # forces the engine (at the synchronous knobs it is bitwise-identical
    # to the masked round -- tests/test_staleness.py pins this), False
    # keeps delay = silence.
    async_rounds: bool | str = "auto"
    # Straggler deadline, in rounds: a delayed client whose drawn lateness
    # exceeds it is demoted to the silence contract AT PLAN TIME (its
    # uplink never enters the stale buffer).  inf = wait for any lateness.
    deadline: float = float("inf")
    # Admission bound on arriving stale uplinks: a row that is s rounds
    # late is admitted iff s <= max_staleness, else dropped (the u_hat
    # cache covers the client).  0 = admit nothing (synchronous point).
    max_staleness: int = 0
    # Staleness discount: an admitted row s rounds late is mixed toward the
    # server's cached view with weight stale_gamma**s.
    stale_gamma: float = 0.5
    # Residual-based early termination (core.autotune): the round emits the
    # fused residual norms ||x - x_prev||^2 / ||x||^2 (ops.residual_norm)
    # and the HOST driver stops once the relative fixed-point residual
    # ||x - x_prev|| / ||x|| stays below ``tol`` for ``patience``
    # consecutive rounds (pfb-clean's primal_dual stopping rule).  tol = 0
    # disables the check AND the metric -- the gate is a static Python
    # decision, so a tol=0 round compiles to the identical fixed-budget
    # graph (the same pattern as the async engine's w > 0 guard).
    tol: float = 0.0
    patience: int = 1

    def __post_init__(self):
        # stepsize / inner-loop hyper-parameters fail AT PARSE TIME with the
        # field name -- an eta <= 0 or K < 1 otherwise only surfaces as NaN
        # rounds (or a ZeroDivisionError in resolved_rho) deep inside the
        # jitted driver
        if self.inner_steps < 1:
            raise ValueError(
                f"inner_steps must be >= 1, got {self.inner_steps}")
        if isinstance(self.eta, str):
            if self.eta != "auto":
                raise ValueError(
                    f"eta must be a positive stepsize, a tuple of them, or "
                    f"'auto', got {self.eta!r}")
        elif isinstance(self.eta, tuple):
            if not self.eta or any(
                    not (isinstance(e, (int, float)) and e > 0.0)
                    for e in self.eta):
                raise ValueError(
                    f"eta tuple must hold one positive per-client stepsize "
                    f"per row, got {self.eta!r}")
        elif not (isinstance(self.eta, (int, float)) and self.eta > 0.0):
            raise ValueError(
                f"eta must be a positive stepsize, got {self.eta!r}")
        if self.rho is not None and not self.rho > 0.0:
            raise ValueError(
                f"rho must be a positive penalty (or None for the 1/(K*eta) "
                f"default), got {self.rho}")
        if not self.tol >= 0.0:
            raise ValueError(
                f"tol must be >= 0 (0 disables early termination), got "
                f"{self.tol}")
        if self.patience < 1:
            raise ValueError(
                f"patience must be >= 1 consecutive sub-tol rounds, got "
                f"{self.patience}")
        if not (0.0 < self.participation <= 1.0):
            raise ValueError(
                f"participation must be in (0, 1], got {self.participation}")
        if self.cohort not in (True, False, "auto"):
            raise ValueError(
                f"cohort must be True, False or 'auto', got {self.cohort!r}")
        if self.cohort_tile is not None and self.cohort_tile < 1:
            raise ValueError(
                f"cohort_tile must be a positive tile size or None, got "
                f"{self.cohort_tile}")
        if self.popstore not in (True, False, "auto"):
            raise ValueError(
                f"popstore must be True, False or 'auto', got "
                f"{self.popstore!r}")
        if self.popstore_min_clients < 1:
            raise ValueError(
                f"popstore_min_clients must be >= 1, got "
                f"{self.popstore_min_clients}")
        if self.screen not in (True, False, "auto"):
            raise ValueError(
                f"screen must be True, False or 'auto', got {self.screen!r}")
        if self.async_rounds not in (True, False, "auto"):
            raise ValueError(
                f"async_rounds must be True, False or 'auto', got "
                f"{self.async_rounds!r}")
        if not self.deadline > 0.0:
            raise ValueError(
                f"deadline must be a positive round count (inf = no "
                f"deadline), got {self.deadline}")
        if self.max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {self.max_staleness}")
        if not (0.0 < self.stale_gamma <= 1.0):
            raise ValueError(
                f"stale_gamma must be in (0, 1], got {self.stale_gamma}")
        # cohort_tile must divide the cohort size (core.api.map_cohort_tiles
        # would only raise at trace time, deep inside a jit).  Checkable here
        # whenever the population is known; a tile >= the cohort is fine --
        # the tiled map degenerates to one shot.
        if (self.cohort_tile is not None and self.num_clients is not None
                and self.participation < 1.0):
            # the engine's single source of truth for the cohort size --
            # duplicating the ceil here once overcounted by one on exact
            # products like 0.07*100 (local import: core imports configs)
            from repro.core.tree_util import cohort_count
            mc = cohort_count(self.num_clients, self.participation)
            if self.cohort_tile < mc and mc % self.cohort_tile:
                raise ValueError(
                    f"cohort_tile={self.cohort_tile} does not divide the "
                    f"cohort size {mc} (= ceil(participation="
                    f"{self.participation} * num_clients={self.num_clients}))")


# ---------------------------------------------------------------------------
# Architecture configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # None -> d_model // n_heads

    # Repeating block pattern.  Entries: "dense" (attn+mlp), "moe" (attn+moe),
    # "rwkv" (rwkv6 time-mix + channel-mix), "rec" (RG-LRU block + mlp),
    # "local" (local/sliding-window attn + mlp).
    block_pattern: Tuple[str, ...] = ("dense",)

    # --- attention ---
    attn_kind: str = "gqa"  # gqa | mla | none
    rope_theta: float = 10_000.0
    window: Optional[int] = None  # sliding-window size for "local" blocks /
    #                               sw-variant of dense archs (long_500k)

    # --- MLA (deepseek v2) ---
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: Optional[int] = None  # per-expert hidden; None -> d_ff
    first_dense_layers: int = 0  # leading dense layers (deepseek v2)
    moe_fused_dispatch: bool = False  # one dispatch for all top-k slots + a
    #   single bf16 expert-combine psum instead of k f32 ones (SSPerf H1)

    # --- norm / misc ---
    norm_kind: str = "rmsnorm"  # rmsnorm | layernorm | nonparam_ln
    tie_embeddings: bool = False
    act: str = "silu"  # silu (swiglu) | gelu (geglu)

    # --- recurrent ---
    rec_d_state: int = 0  # RG-LRU recurrent width (0 -> d_model)
    conv_width: int = 4  # temporal conv width in RG-LRU block
    wkv_head_dim: int = 64  # rwkv6 head size

    # --- modality frontends (STUBS: precomputed embeddings by input_specs) ---
    frontend: Optional[str] = None  # vision | audio | None
    n_prefix_tokens: int = 0  # image patches / audio frames per sample
    frontend_dim: int = 0  # ViT / codec feature dim
    n_codebooks: int = 1  # musicgen parallel codebooks

    # --- serving ---
    shard_cache_seq: bool = False  # SSPerf H2: shard the KV-cache seq dim over
    #   "model" when the head dim cannot (GQA kv < model axis, or MLA)
    subquadratic: bool = False  # eligible for long_500k as-is
    sw_variant_window: Optional[int] = None  # if set, long_500k runs with this
    #                                          sliding window (dense archs)

    # --- distribution ---
    fed: FederatedConfig = field(default_factory=FederatedConfig)
    remat: bool = True  # checkpoint each layer unit in training: its weight
    #   matmuls' outputs (q/k/v/o projections, the MLP's three) are saved, and
    #   the backward pass recomputes only the norms, RoPE, activations and
    #   attention's score and value products (models/stack.py)
    scan_layers: bool = True
    microbatch: Optional[int] = None  # split the per-client batch into this
    #   many grad-accumulation chunks inside each inner step (activation
    #   memory / microbatch, same FLOPs; see EXPERIMENTS.md SSPerf)
    dtype: str = "bfloat16"  # compute: activations and matmul operands
    state_dtype: Optional[str] = None  # stored weights, the federated state;
    #   None -> dtype.  Set apart from dtype for mixed precision: the forward
    #   casts the stored weights to dtype once, at entry (models/model.py)
    source: str = ""  # citation

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def resolved_state_dtype(self) -> str:
        return self.state_dtype or self.dtype

    @property
    def pattern_len(self) -> int:
        return len(self.block_pattern)

    @property
    def n_units(self) -> int:
        return self.n_layers // self.pattern_len

    @property
    def tail_blocks(self) -> Tuple[str, ...]:
        """Blocks for layers beyond the last full pattern unit."""
        rem = self.n_layers % self.pattern_len
        return self.block_pattern[:rem]

    @property
    def supports_decode(self) -> bool:
        return True  # all assigned archs are decoder-style

    def supports_shape(self, shape: ShapeConfig) -> bool:
        if shape.name == "long_500k":
            return self.subquadratic or self.sw_variant_window is not None
        return True

    # ------------------------------------------------------------------
    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests."""
        pat = self.block_pattern
        # keep one full pattern unit (or 2 layers for singleton patterns)
        n_layers = max(2, len(pat))
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        # keep the GQA ratio flavour when possible
        if self.n_kv_heads < self.n_heads:
            n_kv = max(1, n_heads // max(1, self.n_heads // self.n_kv_heads))
        head_dim = d_model // n_heads
        return replace(
            self,
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            moe_d_ff=None if self.moe_d_ff is None else min(self.moe_d_ff, 128),
            vocab_size=min(self.vocab_size, 512),
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2) if self.top_k else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            first_dense_layers=min(self.first_dense_layers, 1),
            kv_lora_rank=min(self.kv_lora_rank, 64),
            q_lora_rank=min(self.q_lora_rank, 64),
            rope_head_dim=min(self.rope_head_dim, 32) if self.kv_lora_rank else self.rope_head_dim,
            nope_head_dim=min(self.nope_head_dim, 32),
            v_head_dim=min(self.v_head_dim, 32),
            rec_d_state=min(self.rec_d_state, 256) if self.rec_d_state else 0,
            wkv_head_dim=min(self.wkv_head_dim, 32),
            window=min(self.window, 64) if self.window else None,
            sw_variant_window=min(self.sw_variant_window, 64) if self.sw_variant_window else None,
            n_prefix_tokens=min(self.n_prefix_tokens, 16) if self.n_prefix_tokens else 0,
            frontend_dim=min(self.frontend_dim, 64) if self.frontend_dim else 0,
            dtype="float32",
            remat=False,
            scan_layers=True,
        )

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS and memory napkin math)."""
        d, v = self.d_model, self.vocab_size
        hd = self.resolved_head_dim
        total = v * d  # embed
        if not self.tie_embeddings:
            total += v * d
        if self.n_codebooks > 1:
            total += (self.n_codebooks - 1) * 2 * v * d
        if self.frontend == "vision":
            total += self.frontend_dim * d + d * d  # 2-layer projector
        per_block: dict[str, int] = {}
        attn_p = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        if self.attn_kind == "mla":
            qd = self.q_lora_rank or d
            attn_p = 0
            if self.q_lora_rank:
                attn_p += d * self.q_lora_rank
            attn_p += qd * self.n_heads * (self.nope_head_dim + self.rope_head_dim)
            attn_p += d * (self.kv_lora_rank + self.rope_head_dim)
            attn_p += self.kv_lora_rank * self.n_heads * (self.nope_head_dim + self.v_head_dim)
            attn_p += self.n_heads * self.v_head_dim * d
        mlp_p = 3 * d * self.d_ff
        per_block["dense"] = attn_p + mlp_p
        per_block["local"] = attn_p + mlp_p
        moe_ff = self.moe_d_ff or self.d_ff
        per_block["moe"] = (
            attn_p
            + self.n_experts * 3 * d * moe_ff
            + self.n_shared_experts * 3 * d * moe_ff
            + d * self.n_experts  # router
        )
        # rwkv6 block: r,k,v,g,w,o projections + channel mix
        per_block["rwkv"] = 6 * d * d + 3 * d * self.d_ff
        # rg-lru block: in/out proj x2 branches + conv + recurrent gates + mlp
        d_rnn = self.rec_d_state or d
        per_block["rec"] = 2 * d * d_rnn + d_rnn * d + self.conv_width * d_rnn + 2 * d_rnn * d_rnn // 8 + mlp_p
        for i in range(self.n_layers):
            blk = self.block_pattern[i % self.pattern_len]
            if blk in ("dense", "moe") and i < self.first_dense_layers:
                total += per_block["dense"]
            else:
                total += per_block[blk]
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k + shared only)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        moe_ff = self.moe_d_ff or self.d_ff
        n_moe_layers = sum(
            1
            for i in range(self.n_layers)
            if self.block_pattern[i % self.pattern_len] == "moe" and i >= self.first_dense_layers
        )
        inactive = n_moe_layers * (self.n_experts - self.top_k) * 3 * self.d_model * moe_ff
        return full - inactive


def validate(cfg: ArchConfig) -> None:
    assert cfg.n_heads % cfg.n_kv_heads == 0, (cfg.name, "GQA ratio")
    if cfg.family == "moe":
        assert cfg.n_experts > 0 and cfg.top_k > 0, cfg.name
    if cfg.attn_kind == "mla":
        assert cfg.kv_lora_rank > 0, cfg.name
    for b in cfg.block_pattern:
        assert b in ("dense", "moe", "rwkv", "rec", "local"), b
