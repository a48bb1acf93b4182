"""Mesh-aware activation sharding constraints and per-client mapping.

Model code is mesh-agnostic: ``constrain(x, None, None, "model")`` is a no-op
when no mesh is active (CPU smoke tests) or when the named axes don't exist /
don't divide the dim; under ``jax.set_mesh(production_mesh)`` it pins the
activation layout so GSPMD doesn't materialise unsharded giants (the
vocab-sharded logits constraint alone is worth ~13 GiB/device on olmo-1b).

``per_client`` runs a function on each device's own client rows when the
active mesh shards the client dim (the ``client_axis`` layout,
``launch/steps.py``): the compiler cannot partition a Pallas kernel, and a
``lax.map`` over a sharded client dim would gather every row to every device.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P


def _auto_axes(mesh) -> set:
    """Axes a constraint may name: inside ``shard_map`` they are manual."""
    return set(mesh.axis_names) - set(mesh.manual_axes)


def constrain(x, *axes):
    """axes: one entry per dim of x -- a mesh-axis name, tuple of names, or
    None.  Silently no-ops outside a ``jax.set_mesh`` context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = _auto_axes(mesh)
    spec = []
    for dim, ax in zip(x.shape, axes):
        cand = (ax,) if isinstance(ax, str) else tuple(ax) if ax else ()
        if cand and set(cand) <= names:
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            spec.append(ax if dim % size == 0 else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _client_axes(m: int):
    """Mesh axes that shard the client dim inside ``jax.set_mesh``: the
    ``(pod,) data`` axes when they split ``m`` evenly, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    names = _auto_axes(mesh)
    axes = tuple(a for a in ("pod", "data") if a in names)
    size = math.prod(mesh.shape[a] for a in axes)
    return axes if size > 1 and m % size == 0 else None


def per_client(fn, rows, shared=()):
    """``fn(*rows, *shared)`` for a function whose output rows depend only on
    the same client's rows.  ``rows`` lead with the client dim m (None
    entries pass through); ``shared`` are replicated operands (server rows).
    Under a mesh whose client axes split m, each device applies ``fn`` to
    its own clients; otherwise this is the plain call."""
    m = next(r for r in rows if r is not None).shape[0]
    axes = _client_axes(m)
    if axes is None:
        return fn(*rows, *shared)
    live = [i for i, r in enumerate(rows) if r is not None]

    def local(*args):
        full = [None] * len(rows)
        for i, a in zip(live, args[:len(live)]):
            full[i] = a
        return fn(*full, *args[len(live):])

    return jax.shard_map(
        local, in_specs=(P(axes),) * len(live) + (P(),) * len(shared),
        out_specs=P(axes), check_vma=False,
    )(*[rows[i] for i in live], *shared)
