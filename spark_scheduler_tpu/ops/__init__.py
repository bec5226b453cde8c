"""XLA compute kernels: capacity, node sorting, bin-packing strategies.

Every kernel is a pure jittable function over `ClusterTensors` + app-shape
arrays. The five packing strategies of the reference
(internal/extender/binpack.go:39-54) are reproduced with slot-exact placement
semantics, but as vectorized tensor programs (prefix sums / sorts /
searchsorted) instead of per-slot greedy loops.
"""

from spark_scheduler_tpu.ops.packing import (  # noqa: F401
    Packing,
    spark_bin_pack,
    tightly_pack,
    distribute_evenly,
    minimal_fragmentation,
    single_az_tightly_pack,
    single_az_minimal_fragmentation,
    az_aware_tightly_pack,
    BINPACK_FUNCTIONS,
    SINGLE_AZ_PACKERS,
)
from spark_scheduler_tpu.ops.capacity import node_capacities, fits  # noqa: F401
from spark_scheduler_tpu.ops.pallas_window import pallas_available  # noqa: F401
from spark_scheduler_tpu.ops.sorting import priority_order  # noqa: F401
from spark_scheduler_tpu.ops.efficiency import avg_packing_efficiency  # noqa: F401
