"""Federated GPDMM training of a dense decoder LM (OLMo), one silo per
client, on one chip: ``repro.core.make(cfg).round`` with the client
gradient ``jax.grad`` of ``model.loss`` over the client's batch, as
``repro.launch.train.run`` defines it, jitted with the state donated.

Each round takes one batch per silo from a pool of token batches made on
the device from the seed: Zipf(1.1) unigram streams, the vocabulary
permuted per silo (one topic per silo).  The K inner steps of a round share
the round's batch, as in ``launch/train.run``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import compare, counts
from chipbench.objectives import common
from chipbench.reference import gpdmm as ref_gpdmm
from chipbench.reference import olmo as ref_olmo

WEIGHT_STD = 0.02
ZIPF = 1.1


def token_pool(key, rounds: int, m: int, batch: int, seq_len: int, vocab: int):
    """int32 tokens (rounds, m, batch, seq_len + 1): next-token pairs are
    tokens[..., :-1] -> tokens[..., 1:]."""
    p = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -ZIPF
    cdf = jnp.cumsum(p) / jnp.sum(p)
    topic = jax.random.fold_in(key, 1 << 20)

    def one(r, i):
        u = jax.random.uniform(jax.random.fold_in(jax.random.fold_in(key, r), i),
                               (batch, seq_len + 1))
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        perm = jax.random.permutation(jax.random.fold_in(topic, i), vocab)
        return perm[rank].astype(jnp.int32)

    return jax.vmap(lambda r: jax.vmap(lambda i: one(r, i))(jnp.arange(m)))(
        jnp.arange(rounds))


def split_tokens(toks):
    return {"tokens": toks[..., :-1], "targets": toks[..., 1:]}


class Objective:
    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        self.config, self.traffic, self.devices = config, traffic, devices
        self.m = traffic["clients"]
        self.K = traffic["inner_steps"]
        self.eta = traffic["eta"]
        self.batch = traffic["sequences_per_client"]
        self.seq = traffic["seq_len"]
        self.pool_size = traffic["batch_pool"]
        self.checked = traffic["checked_rounds"]
        self.dtype = common.DTYPES[config["dtype"]]
        self.shapes = ref_olmo.param_shapes(config)
        self.wkey = common.seed_key(seed, 0)
        self.tkey = common.seed_key(seed, 1)
        self.memory = None
        self._weights = None

    # -- the program ------------------------------------------------------
    def weights(self):
        if self._weights is None:
            self._weights = jax.jit(functools.partial(
                common.normal_tree, shapes=self.shapes, dtype=self.dtype,
                std=WEIGHT_STD))
        return self._weights(self.wkey)

    def tokens(self):
        return jax.jit(functools.partial(
            token_pool, rounds=self.pool_size, m=self.m, batch=self.batch,
            seq_len=self.seq, vocab=self.config["vocab_size"]))(self.tkey)

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
            tie_embeddings=c["tie_embeddings"], dtype=c["dtype"])
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
            return fed.round(state, client_grad, split_tokens(toks))

        self.state = jax.jit(lambda p: fed.init(p, self.m),
                             donate_argnums=0)(self.weights())
        self.pool = self.tokens()
        step = jax.jit(one_round, donate_argnums=(0,)).lower(
            self.state, self.pool).compile()
        self.memory = step.memory_analysis()
        self.step_fn = step
        spec = arena.ArenaSpec.from_tree(pshapes)
        self.read_fn = jax.jit(lambda st, x0: compare.readings(
            x0, st["x_s"], spec.unpack_stacked(st["x_c"]),
            spec.unpack_stacked(st["lam_s"])))

    def step(self):
        self.state, metrics = self.step_fn(self.state, self.pool)
        return metrics

    def readings(self):
        return common.host(self.read_fn(self.state, self.weights()))

    def close(self):
        self.state = self.step_fn = None

    # -- the plain reference ------------------------------------------------
    def reference(self, store: str, fault: str | None = None):
        """Readings of the plain reference over the checked rounds, its
        state stored in ``store``.  ``fault="half_batch"`` leaves out the
        second half of every client's tokens, the mean taken over the rest."""
        st = common.DTYPES[store]
        cfg = self.config
        x0 = jax.tree.map(lambda a: a.astype(st), self.weights())
        toks = self.pool if self.pool is not None else self.tokens()
        if fault == "half_batch":
            toks = toks[..., : self.seq // 2 + 1]
        grad_one = functools.partial(ref_olmo.grad, cfg)
        rf = jax.jit(functools.partial(
            ref_gpdmm.round_fn, grad_one=grad_one, K=self.K, eta=self.eta,
            per_step=False, store=st, client_batch=1), donate_argnums=(0,))
        read = jax.jit(lambda s, x0: compare.readings(x0, s["x_s"], s["x_c"], s["lam"]))
        b0 = split_tokens(toks[0])
        g0 = jax.jit(lambda x, b: jnp.mean(jax.lax.map(
            lambda bi: compare.leaf_norms(grad_one(
                jax.tree.map(lambda a: a.astype(jnp.float32), x), bi)), b), axis=0))(x0, b0)
        state = ref_gpdmm.init(x0, self.m)
        rounds, drift = [], []
        for r in range(self.checked):
            state, d = rf(state, split_tokens(toks[r]))
            rounds.append(common.host(read(state, x0)))
            drift.append(float(d))
        return {"rounds": rounds, "drift": drift, "grad0": common.host(g0)}

    # -- the work of a round -----------------------------------------------
    def counts(self):
        n = counts.lm_params(self.config)
        b = jnp.dtype(self.dtype).itemsize
        m_dev = self.m // len(self.devices)
        return {
            "flops_per_round": counts.lm_flops(
                self.config, self.m * self.K * self.batch, self.seq),
            "kernels": {
                "fused_update_arena": {"bytes": counts.fused_update_bytes(m_dev, n, b),
                                       "calls_per_round": self.K},
                "round_tail": {"bytes": counts.round_tail_bytes(m_dev, n, b),
                               "calls_per_round": 1},
            },
            # the ops jax.grad traces carry jvp(...) / transpose(...) scopes
            "grad_ops": {"tf_op": ["jvp(", "transpose("]},
        }
