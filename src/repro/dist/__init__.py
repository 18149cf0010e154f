"""repro.dist — the distributed layer (DESIGN.md §7).

Two submodules:

* ``sharding`` — ``NamedSharding`` pytrees for the model zoo's param /
  optimizer / batch / KV-cache trees (FSDP over the ``pod``/``data``
  axes, tensor-parallel over ``model``), plus the ``shard_program``
  lifter the sharded serving engine uses to spread request batches over
  the ``data`` axis of a mesh.
* ``moe_ep`` — explicit expert-parallel MoE via ``shard_map``: expert
  FFNs partitioned over the ``model`` axis (with a replica path when
  there are more devices than experts), numerically equivalent to the
  GSPMD ``models.common.moe_layer`` and differentiable end to end.

Everything accepts an explicit ``mesh=`` argument, which is what the
tier-1 tests use; the ambient-mesh paths read ``jax.sharding.set_mesh``
or a ``with mesh:`` context.
"""
from . import moe_ep, sharding

__all__ = ["moe_ep", "sharding"]
