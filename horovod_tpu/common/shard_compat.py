"""The one import point for ``shard_map`` and ``axis_size``: every
shard_map user in this codebase imports them from here (directly, or
via ``parallel._shard_map`` / ``ops.xla_ops`` which re-export them), so
a move in jax's API is repaired in exactly one place."""

from jax import shard_map  # noqa: F401
from jax.lax import axis_size  # noqa: F401
