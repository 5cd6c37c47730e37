from .rules import (ShardingRules, DEFAULT_RULES, logical_spec, shard,
                    use_weight, set_active_layout, active_rules,
                    data_axes, model_axis, mesh_axis_sizes,
                    current_mesh, kernel_spec)

__all__ = ["ShardingRules", "DEFAULT_RULES", "logical_spec", "shard", "use_weight", "set_active_layout", "active_rules",
           "data_axes", "model_axis", "mesh_axis_sizes", "current_mesh", "kernel_spec"]
