"""JAX version shim SPI (ref: SparkShims.scala:61 + the per-version shim
layer, sql-plugin/.../shims/).

The reference abstracts Spark's breaking API drift behind a shim
provider chosen at runtime; this engine's moving substrate is JAX, whose
public API drifts the same way (shard_map's home and kwargs, the tree
API's module, pytree registration). Every version-sensitive touchpoint
routes through this package so a JAX upgrade is a one-file change, and
``provider()`` names the resolved shim for diagnostics (the
SparkShimServiceProvider.matchesVersion analog).

One installation is supported (jax 0.9.0): branches for other versions
are added when a second one is, not kept in advance."""

from __future__ import annotations

import jax


def provider() -> str:
    """Human-readable name of the resolved shim set."""
    return f"jax {jax.__version__} (jax-native-shard-map, tree=jax.tree)"


def shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


tree_map = jax.tree.map
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten


def register_pytree_node(cls, flatten, unflatten):
    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
