"""repro.dist — distributed execution: logical-axis sharding rules
(``sharding``), int8 error-feedback gradient all-reduce (``compress``) and
preemption / straggler handling (``fault``).
"""
from repro.dist import compress, fault, sharding
from repro.dist.sharding import (RULES, current_mesh, named_sharding,
                                 override_rules, shard, spec_for,
                                 tree_shardings)

__all__ = [
    "RULES", "compress", "current_mesh", "fault", "named_sharding",
    "override_rules", "shard", "sharding", "spec_for", "tree_shardings",
]
