"""Production mesh construction (DESIGN.md §5).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_local_mesh",
    "make_keys_mesh",
]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the one place meshes are built.

    JAX 0.9's ``make_mesh`` defaults to ``Explicit`` axes, under which
    sharding-in-types rejects the partitioner-driven code here (a plain
    ``jnp.take`` of a sharded embedding raises ``ShardingTypeError``).  Every
    mesh in the repo, tests included, comes from this helper instead.
    """
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), **kwargs
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_keys_mesh(num_shards: int | None = None, *, devices=None):
    """1-D mesh over the ``keys`` axis for row-sharded sketch banks.

    The bank's row axis partitions over it (``sharding.rules.bank_sharding``);
    full mergeability makes the sharded bank one logical bank, so this mesh
    is orthogonal to (and composable with) the model meshes above.

    **Process-spanning:** after ``launch.distributed.initialize`` joins a
    fleet, ``jax.devices()`` enumerates *every* process's devices in a
    consistent global order, so the same call builds the same fleet-wide
    mesh on every host — shard ``i`` is ``mesh.devices.flat[i]``, owned by
    that device's process.  ``num_shards=None`` takes every visible device
    (local and remote alike); an explicit ``num_shards`` smaller than the
    fleet takes the first ``num_shards`` devices, and only processes owning
    one of them may drive the resulting engines (the SPMD contract).
    """
    devs = jax.devices() if devices is None else list(devices)
    n = len(devs) if num_shards is None else int(num_shards)
    if not 1 <= n <= len(devs):
        raise ValueError(f"num_shards={n} outside [1, {len(devs)}] visible devices")
    return make_mesh((n,), ("keys",), devices=devs[:n])


def make_local_mesh(model: int = 1):
    """Mesh over whatever devices exist (CPU smoke runs, elastic restarts).

    Elastic rescale: callers re-invoke this after device loss; the data axis
    shrinks to the surviving device count (train.py re-lowers against it).
    """
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return make_mesh((n // model, model), ("data", "model"))

