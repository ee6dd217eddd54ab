"""Parallelism substrate: sharding rules, logical axes, collective helpers,
and the one seam for the JAX collective API (``repro.par.compat``)."""
from repro.par import compat
from repro.par.compat import abstract_mesh, axis_size, mark_varying, shard_map
from repro.par.sharding import (
    LOGICAL_AXES, ShardingRules, logical_to_physical, spec_for,
    param_specs, named_shardings, data_spec, replicated,
)

__all__ = ["LOGICAL_AXES", "ShardingRules", "logical_to_physical", "spec_for",
           "param_specs", "named_shardings", "data_spec", "replicated",
           "compat", "shard_map", "mark_varying", "abstract_mesh", "axis_size"]
