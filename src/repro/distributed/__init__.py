"""``repro.distributed`` — mesh, sharding and cross-device helpers.

Module map
----------
``context.py``      :class:`DistContext` (mesh + dp/ep/tp axis
                    assignment, divisibility-aware activation
                    constraints) and :func:`make_serving_context`, the
                    dp x ep mesh builder the serving engine uses.
``sharding.py``     Logical-axis parameter/batch/cache sharding rules
                    with divisibility fallback (:class:`Rules`,
                    :func:`make_rules`, tree-level helpers).
``compression.py``  int8 gradient compression with error feedback for
                    cross-pod reduction.

Meshes are built by ``repro.compat.make_mesh`` (``Auto`` axis types,
which the sharding rules assume). See ``docs/distributed.md`` for the
serving mesh layout.
"""
from repro.distributed.context import (DistContext, constrain, ep_split,
                                       make_serving_context)

__all__ = ["DistContext", "constrain", "ep_split", "make_serving_context"]
