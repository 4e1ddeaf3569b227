"""The tree's one import site for ``shard_map`` and ``axis_size`` (library
modules and tests), so a future jax move is one edit:

    from paddle_tpu._jax_compat import shard_map
"""
from jax import shard_map
from jax.lax import axis_size

__all__ = ["axis_size", "shard_map"]
