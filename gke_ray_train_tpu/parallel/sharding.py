"""Pytree sharding helpers.

The reference's FSDP/ZeRO story is delegated to DDP + bitsandbytes paged
optimizers (SURVEY.md rows D4/D5). Here sharded data-parallelism is purely
declarative: every param pytree travels with a matching pytree of
``PartitionSpec``; placing params/optimizer state is one ``jax.device_put``.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@contextlib.contextmanager
def sharding_invariant_rng():
    """Partitionable threefry for the duration: random draws made inside
    are IDENTICAL however — and whether — their outputs are sharded.

    Under non-partitionable threefry a jitted draw's VALUES depend on
    its ``out_shardings`` (kernelcheck's differential sweeps caught
    meshed ``init_params`` diverging from the plain oracle by ~3
    init-stds). Every init path wraps itself in this context, making
    meshed init == plain init == init on ANY topology (the elastic
    same-seed-any-pool contract, PR 8) an invariant of the code and not
    of a global flag. The installed jax 0.9 already defaults the flag
    to True, so the scope changes nothing today; it stays because
    ``tests/test_kernelcheck.py`` pins it."""
    old = bool(jax.config.jax_threefry_partitionable)
    jax.config.update("jax_threefry_partitionable", True)
    try:
        yield
    finally:
        jax.config.update("jax_threefry_partitionable", old)


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    """Map a pytree of PartitionSpec into a pytree of NamedSharding."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_tree(tree: Any, mesh: Mesh, spec_tree: Any) -> Any:
    """Place a (host-local) pytree onto the mesh per its spec tree."""
    return jax.device_put(tree, tree_shardings(mesh, spec_tree))


def constrain(x: Any, mesh: Mesh, *spec) -> Any:
    """with_sharding_constraint under an explicit mesh."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n`` (shape padding for even
    sharding: vocab / ffn dims must divide the model axis)."""
    return ((n + m - 1) // m) * m
