"""Softmax regression in plain ``jax.numpy`` (arXiv:2107.08809, Table I).

Parameters are two leaves: the weight matrix W (F, C) and the bias b (C,).
The loss is the mean cross-entropy of softmax(x W + b) over the batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def loss(params, x, y):
    logits = jnp.matmul(x, params["W"], precision=HIGHEST) + params["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def grad(params, batch):
    """Gradient of ``loss`` over one client's batch {x (B, F), y (B,)}."""
    return jax.grad(loss)(params, batch["x"], batch["y"])
