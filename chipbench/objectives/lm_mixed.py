"""Federated GPDMM training of a dense decoder LM (OLMo) at mixed
precision, as OLMo trains (arXiv:2402.00838, section 3): the weights, the
client state and the duals stored in the configuration's ``dtype``
(float32), the forward and backward passes computed in its
``compute_dtype`` (bfloat16).  The program is built as
``repro.launch.train.run`` builds it: the architecture carries both dtypes,
``repro.models.build`` casts the stored weights once at the forward's
entry, and ``repro.core.make(cfg).round`` keeps the state in a float32
arena.  The token pool is ``lm``'s.

The comparison is round by round: the reference runs each checked round
from the state the program started that round in, and every reading is
taken from that round's starting server point.  So round 3 checks one
round whose duals are not zero.  Over whole trajectories the two part
however right the program is: the gradient computed in bfloat16 differs
from the float32 reference's by about 1 % per element, and at ``silo2``'s
step three rounds amplify that to gaps of up to a third on some seeds; the
reference driven by the program's own gradient reads the same gaps
(PERF.md, section 2).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, counts
from chipbench.objectives import common, lm
from chipbench.reference import gpdmm as ref_gpdmm
from chipbench.reference import olmo as ref_olmo


class Objective(lm.Objective):
    def setup(self):
        from repro.configs import get_arch
        from repro.configs.base import FederatedConfig
        from repro.core import arena, make
        from repro.models import build

        c = self.config
        arch = dataclasses.replace(
            get_arch(c["arch"]), n_layers=c["n_layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
            d_ff=c["d_ff"], vocab_size=c["vocab_size"], rope_theta=c["rope_theta"],
            tie_embeddings=c["tie_embeddings"], dtype=c["compute_dtype"],
            state_dtype=c["dtype"])
        model = build(arch)
        pshapes = jax.eval_shape(model.init, jax.random.key(0))
        common.same_layout(pshapes, self.shapes, self.dtype)
        fed = make(FederatedConfig(
            algorithm=self.traffic["algorithm"], inner_steps=self.K,
            eta=self.eta, num_clients=self.m, layout="client_axis",
            participation=self.traffic["participation"]))

        def client_grad(p, b):
            return jax.grad(lambda q: model.loss(q, b)[0])(p)

        pool_n = self.pool_size

        def one_round(state, pool):
            toks = jax.lax.dynamic_index_in_dim(pool, state["round"] % pool_n,
                                                keepdims=False)
            return fed.round(state, client_grad, lm.split_tokens(toks))

        self.state = jax.jit(lambda p: fed.init(p, self.m),
                             donate_argnums=0)(self.weights())
        self.pool = self.tokens()
        step = jax.jit(one_round, donate_argnums=(0,)).lower(
            self.state, self.pool).compile()
        self.memory = step.memory_analysis()
        self.step_fn = step
        self.spec = spec = arena.ArenaSpec.from_tree(pshapes)
        self.read_fn = jax.jit(lambda st, x0: compare.readings(
            x0, st["x_s"], spec.unpack_stacked(st["x_c"]),
            spec.unpack_stacked(st["lam_s"])))
        # host copies of the state each checked round after the first starts
        # from, as {x_s, x_c, lam} with the clients still packed
        self.starts, self.stepped = [], 0

    def step(self):
        if 0 < self.stepped < self.checked:
            st = self.state
            self.starts.append(jax.device_get(
                {"x_s": st["x_s"], "x_c": st["x_c"], "lam": st["lam_s"]}))
        self.stepped += 1
        return super().step()

    def start_x_s(self, r: int):
        """The server point checked round ``r`` (from 0) started from."""
        if r == 0:
            return self.weights()
        return jax.device_put(self.starts[r - 1]["x_s"], self.devices[0])

    def start_state(self, r: int, store):
        """The program's state at checked round ``r``'s start as the
        reference's {x_s, x_c, lam} pytrees stored in ``store``.  The
        clients are unpacked on the host and put on the device leaf by
        leaf: the packed and the unpacked state do not fit on it together."""
        if r == 0:
            x0 = jax.tree.map(lambda a: a.astype(store), self.weights())
            return ref_gpdmm.init(x0, self.m)
        s, dev = self.starts[r - 1], self.devices[0]
        put = lambda a: jax.device_put(np.asarray(a).astype(store), dev)  # noqa: E731

        def unpack(a):
            return jax.tree.unflatten(self.spec.treedef, [
                put(a[:, e.offset:e.offset + e.size].reshape((a.shape[0],) + e.shape))
                for e in self.spec.leaves])

        return {"x_s": jax.tree.map(put, s["x_s"]), "x_c": unpack(s["x_c"]),
                "lam": unpack(s["lam"])}

    def readings(self):
        r = len(self.starts)
        return common.host(self.read_fn(self.state, self.start_x_s(r)))

    # -- the plain reference ------------------------------------------------
    def reference(self, store: str, fault: str | None = None):
        """Readings of the plain reference, each checked round run from the
        program's state at that round's start, cast to ``store``.
        ``fault="half_batch"`` leaves out the second half of every client's
        tokens."""
        st = common.DTYPES[store]
        cfg = self.config
        toks = self.pool if self.pool is not None else self.tokens()
        if fault == "half_batch":
            toks = toks[..., : self.seq // 2 + 1]
        rf = jax.jit(functools.partial(
            ref_gpdmm.round_fn, grad_one=functools.partial(ref_olmo.grad, cfg), K=self.K, eta=self.eta,
            per_step=False, store=st, client_batch=1), donate_argnums=(0,))
        read = jax.jit(lambda s, x0: compare.readings(x0, s["x_s"], s["x_c"], s["lam"]))
        x0 = self.weights()
        b0 = lm.split_tokens(toks[0])
        g0 = jax.jit(lambda x, b: jnp.mean(jax.lax.map(
            lambda bi: compare.leaf_norms(ref_olmo.grad(cfg, x, bi)), b), axis=0))(x0, b0)
        rounds, drift = [], []
        for r in range(self.checked):
            state = self.start_state(r, st)
            state, d = rf(state, lm.split_tokens(toks[r]))
            rounds.append(common.host(read(state, self.start_x_s(r))))
            drift.append(float(d))
            del state
        return {"rounds": rounds, "drift": drift, "grad0": common.host(g0)}

    def counts(self):
        """As ``lm``'s, with each kernel's bytes at the dtype its operands
        have: the state, the duals, the server row and the packed gradient
        (the cast's transpose returns it in the state dtype) all in
        ``dtype``.  The program applies eq. (20) a client at a time, as each
        gradient is made: K m calls a round, which together need the bytes
        of K whole-arena steps."""
        out = super().counts()
        n = counts.lm_params(self.config)
        b = jnp.dtype(self.dtype).itemsize
        m_dev = self.m // len(self.devices)
        out["kernels"] = {
            "fused_update_arena": {"bytes": counts.fused_update_bytes(m_dev, n, b) / m_dev,
                                   "calls_per_round": self.K * m_dev},
            "round_tail": {"bytes": counts.round_tail_bytes(m_dev, n, b),
                           "calls_per_round": 1},
        }
        return out
