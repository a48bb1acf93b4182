"""Pieces the objectives share: keys from large seeds, weights from a seed,
and the ``Objective`` protocol the harness drives."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float8_e4m3fn": jnp.float8_e4m3fn}
# the control: the precision next below the one a configuration states
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def seed_key(seed: int, stream: int):
    """A key for one stream of a run.  Seeds may exceed 32 bits, and
    ``jax.random.key`` keeps only the low 32, so the high bits are folded
    in on top."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def normal_tree(key, shapes, dtype, std: float):
    """A pytree of N(0, std^2) leaves shaped by ``shapes`` (a nested dict of
    shape tuples), leaf i drawn from fold_in(key, i), stored in ``dtype``."""
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
    out = [(jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32) * std
            ).astype(dtype) for i, s in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


def same_layout(program_shapes, shapes, dtype) -> None:
    """Raise unless the program's parameter tree has the reference layout."""
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    want = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype), shapes,
                        is_leaf=is_shape)
    got = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                       program_shapes)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError(f"program parameters {got} differ from the "
                         f"configuration's layout {want}")


def host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))

