"""Federated GPDMM on softmax regression (the paper's Table I objective)
over a population whose data lives on the device: the program's
``SoftmaxRegression(F, C).oracle()``, whose gradient runs on the packed
client arena, in ``repro.core.make(cfg).round`` with per-step mini-batches,
jitted with the state donated.

Inner step k of round r takes, on every client, the samples
[s, s + B) with s = ((r K + k) B) mod (n - B + 1): the paper's
deterministic mini-batch order.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import compare, counts
from chipbench.objectives import common
from chipbench.reference import gpdmm as ref_gpdmm
from chipbench.reference import softmax as ref_softmax

WEIGHT_STD = 0.01
SEP = 0.12
RANK = 32
HOME_SHARE = 0.5
SCALE = 0.1
GEN_BLOCK = 50  # clients generated per step of the data map


def client_data(key, m: int, n: int, F: int, C: int):
    """x (m, n, F) f32 and y (m, n) int32: class-skewed Gaussian-mixture
    images.  Client i draws half its labels from its home class i mod C and
    half uniformly; an image is its class mean plus a rank-32 style
    component plus unit noise, scaled by 1/10."""
    kc, kb, kd = jax.random.split(key, 3)
    means = jax.random.normal(kc, (C, F)) * SEP
    basis = jax.random.normal(kb, (F, RANK)) / math.sqrt(F)

    def one(i):
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(kd, i), 4)
        y = jnp.where(jax.random.uniform(k1, (n,)) < HOME_SHARE, i % C,
                      jax.random.randint(k2, (n,), 0, C)).astype(jnp.int32)
        z = jax.random.normal(k3, (n, RANK))
        x = means[y] + 2.0 * jnp.einsum("nr,fr->nf", z, basis) + \
            jax.random.normal(k4, (n, F))
        return x * SCALE, y

    block = math.gcd(m, GEN_BLOCK)
    return jax.lax.map(one, jnp.arange(m), batch_size=block)


def round_batches(x, y, r, K: int, B: int):
    """Round r's mini-batches, leaves (K, m, B, ...)."""
    n = x.shape[1]
    starts = ((r * K + jnp.arange(K)) * B) % (n - B + 1)
    take = lambda a, s: jax.lax.dynamic_slice_in_dim(a, s, B, axis=1)  # noqa: E731
    return {"x": jax.vmap(lambda s: take(x, s))(starts),
            "y": jax.vmap(lambda s: take(y, s))(starts)}


class Objective:
    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        self.config, self.traffic, self.devices = config, traffic, devices
        self.F, self.C = config["n_features"], config["n_classes"]
        self.m = config["clients"]
        self.n = config["samples_per_client"]
        self.K = traffic["inner_steps"]
        self.eta = traffic["eta"]
        self.B = traffic["batch"]
        self.checked = traffic["checked_rounds"]
        self.dtype = common.DTYPES[config["dtype"]]
        self.dim = self.F * self.C + self.C
        self.wkey = common.seed_key(seed, 0)
        self.dkey = common.seed_key(seed, 1)
        self.memory = None
        self._weights = None

    def weights(self):
        if self._weights is None:
            self._weights = jax.jit(lambda k: (
                jax.random.normal(k, (self.dim,)) * WEIGHT_STD).astype(self.dtype))
        return self._weights(self.wkey)

    def split(self, flat):
        """Flat (..., dim) parameters -> {"W": (..., F, C), "b": (..., C)}."""
        lead = flat.shape[:-1]
        FC = self.F * self.C
        return {"W": flat[..., :FC].reshape(lead + (self.F, self.C)),
                "b": flat[..., FC:self.dim]}

    def setup(self):
        from repro.configs.base import FederatedConfig
        from repro.core import make
        from repro.core.softmax import SoftmaxRegression

        self.x, self.y = jax.jit(functools.partial(
            client_data, m=self.m, n=self.n, F=self.F, C=self.C))(self.dkey)
        oracle = SoftmaxRegression(self.F, self.C).oracle()
        fed = make(FederatedConfig(
            algorithm=self.traffic["algorithm"], inner_steps=self.K,
            eta=self.eta, num_clients=self.m,
            participation=self.traffic["participation"]))
        K, B = self.K, self.B

        def one_round(state, x, y):
            batch = round_batches(x, y, state["round"], K, B)
            return fed.round(state, oracle, batch, True)

        self.state = jax.jit(lambda p: fed.init(p, self.m),
                             donate_argnums=0)(self.weights())
        step = jax.jit(one_round, donate_argnums=(0,)).lower(
            self.state, self.x, self.y).compile()
        self.memory = step.memory_analysis()
        self.step_fn = step
        dim = self.dim
        self.read_fn = jax.jit(lambda st, x0: compare.readings(
            self.split(x0), self.split(st["x_s"]), self.split(st["x_c"][:, :dim]),
            self.split(st["lam_s"][:, :dim])))

    def step(self):
        self.state, metrics = self.step_fn(self.state, self.x, self.y)
        return metrics

    def readings(self):
        return common.host(self.read_fn(self.state, self.weights()))

    def close(self):
        self.state = self.step_fn = None

    def reference(self, store: str, fault: str | None = None):
        """Readings of the plain reference over the checked rounds, its
        state and data stored in ``store``.  ``fault="half_batch"`` leaves
        out the second half of every mini-batch, the mean taken over the
        rest."""
        st = common.DTYPES[store]
        K, B = self.K, self.B
        x0 = jax.tree.map(lambda a: a.astype(st), self.split(self.weights()))

        def batches(x, y, r):
            b = round_batches(x, y, r, K, B)
            if fault == "half_batch":
                b = jax.tree.map(lambda a: a[:, :, : B // 2], b)
            b = {"x": b["x"].astype(st).astype(jnp.float32), "y": b["y"]}
            return jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), b)  # client-major

        rf = jax.jit(lambda s, x, y, r: ref_gpdmm.round_fn(
            s, batches(x, y, r), ref_softmax.grad, K=K, eta=self.eta, per_step=True,
            store=st, client_batch=self.m), donate_argnums=(0,))
        read = jax.jit(lambda s, x0: compare.readings(x0, s["x_s"], s["x_c"], s["lam"]))
        g0 = jax.jit(lambda w, x, y: jnp.mean(compare.leaf_norms(jax.vmap(
            lambda b: ref_softmax.grad(jax.tree.map(lambda a: a.astype(jnp.float32), w),
                                       jax.tree.map(lambda t: t[0], b)))(
            batches(x, y, 0)), 1), axis=0))(x0, self.x, self.y)
        state = ref_gpdmm.init(x0, self.m)
        rounds, drift = [], []
        for r in range(self.checked):
            state, d = rf(state, self.x, self.y, r)
            rounds.append(common.host(read(state, x0)))
            drift.append(float(d))
        return {"rounds": rounds, "drift": drift, "grad0": common.host(g0)}

    def counts(self):
        n = counts.mlr_params(self.config)
        b = jnp.dtype(self.dtype).itemsize
        m_dev = self.m // len(self.devices)
        return {
            "flops_per_round": counts.mlr_flops(self.config, self.m * self.K * self.B),
            "kernels": {
                "fused_update_arena": {"bytes": counts.fused_update_bytes(m_dev, n, b),
                                       "calls_per_round": self.K},
                "round_tail": {"bytes": counts.round_tail_bytes(m_dev, n, b),
                               "calls_per_round": 1},
            },
            # the closed-form arena gradient lives in the program's softmax
            # module; its ops carry that source line
            "grad_ops": {"source": ["repro/core/softmax.py"]},
        }
