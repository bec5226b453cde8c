"""Multi-chip scale-out for the placement solver.

The reference scales by running ONE single-threaded Go process (SURVEY.md §0);
the TPU rebuild scales the 10k-node x 1k-app solve across a device mesh
(SURVEY.md §2d, §5.8): the node axis is sharded like a sequence axis
("sequence parallelism" for this workload) and independent instance-group
subproblems are data-parallel. Collectives are never hand-written — shardings
are declared with `jax.sharding.NamedSharding` and XLA inserts the
psum/all-gather/all-to-all it needs (scaling-book recipe).
"""

from spark_scheduler_tpu.parallel.mesh import make_pool_slots, make_solver_mesh
from spark_scheduler_tpu.parallel.solve import (
    grouped_fifo_pack,
    node_sharding,
    shard_apps,
    sharded_fifo_pack,
    stack_groups,
)

__all__ = [
    "make_pool_slots",
    "make_solver_mesh",
    "node_sharding",
    "shard_apps",
    "sharded_fifo_pack",
    "grouped_fifo_pack",
    "stack_groups",
]
