"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the ``pod``
axis is an outer data-parallel axis whose gradient all-reduce crosses the
inter-pod links once per step.

Defined as functions (not module constants) so importing this module never
touches JAX device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_batch_mesh(devices=None):
    """1-D ``("batch",)`` mesh over ``devices`` (every available device
    when None).

    This is the mesh the batched engines shard their flat batch axis over
    (``repro.engine.population`` flattens D x V x T into one axis and
    splits it across devices with a ``NamedSharding``).  On a single
    device the mesh has one slot and sharding is a transparent no-op.
    """
    devices = jax.devices() if devices is None else list(devices)
    return make_mesh((len(devices),), ("batch",), devices)


def batch_sharding(mesh, ndim: int = 1):
    """``NamedSharding`` that splits the leading axis of an ``ndim``-array
    over the ``batch`` axis of ``mesh`` and replicates the rest."""
    spec = jax.sharding.PartitionSpec("batch", *([None] * (ndim - 1)))
    return jax.sharding.NamedSharding(mesh, spec)


def chunked_batch_sharding(mesh, ndim: int = 2):
    """``NamedSharding`` for a ``[chunks, chunk, ...]`` stacked megabatch
    (``repro.engine.dispatch``): the *resident* chunk axis (axis 1) splits
    over ``batch`` exactly like the un-chunked flat axis would, while the
    chunk-stream axis stays unsharded — ``lax.map`` walks it sequentially.
    Bucket and chunk sizes are ``n_devices * 2**k`` by construction
    (:func:`repro.engine.dispatch.bucket_ladder`), so the split is always
    even on this mesh."""
    spec = jax.sharding.PartitionSpec(None, "batch", *([None] * (ndim - 2)))
    return jax.sharding.NamedSharding(mesh, spec)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the actually-available devices (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (includes ``pod`` when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.axis_names else 1
