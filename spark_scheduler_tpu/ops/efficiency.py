"""Packing-efficiency kernel (binpack/efficiency.go:23-156).

Per-node efficiency = (already-reserved + newly-reserved) / schedulable per
dim; GPU only counts on nodes with schedulable GPU. The average over a
packing's entries (driver + one entry PER executor — duplicate nodes count
once per occurrence, matching chooseBestResult, single_az.go:84-97) scores
zones in the single-AZ packers and feeds the binpack metrics.

Deviation from the reference, recorded deliberately: the Go code divides
`resource.Quantity.Value()`s, which ROUNDS sub-unit quantities (500m CPU ->
1); we divide exact fixed-point units in float32, which is strictly more
accurate. Tie behavior between zones can differ only when the reference's
rounding itself changed the winner.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.models.resources import CPU_DIM, GPU_DIM, MEM_DIM


class AvgEfficiency(NamedTuple):
    cpu: jnp.ndarray
    memory: jnp.ndarray
    gpu: jnp.ndarray
    max: jnp.ndarray  # the field zone selection compares (efficiency.go:36-39)


def new_reservation_tensor(
    num_nodes: int,
    driver_node: jnp.ndarray,
    executor_nodes: jnp.ndarray,
    driver_req: jnp.ndarray,
    exec_req: jnp.ndarray,
) -> jnp.ndarray:
    """[N,3] scatter-add of a packing's tentative reservations."""
    out = jnp.zeros((num_nodes, 3), jnp.int32)
    d_ok = driver_node >= 0
    out = out.at[jnp.clip(driver_node, 0)].add(
        jnp.where(d_ok, driver_req, 0).astype(jnp.int32)
    )
    e_ok = executor_nodes >= 0
    out = out.at[jnp.clip(executor_nodes, 0)].add(
        jnp.where(e_ok[:, None], exec_req[None, :], 0).astype(jnp.int32)
    )
    return out


def avg_packing_efficiency_np(
    schedulable,
    available,
    driver_node: int,
    executor_nodes,
    driver_req,
    exec_req,
) -> AvgEfficiency:
    """Pure-numpy twin of `avg_packing_efficiency` for HOST-side reporting
    (serving path, resource.go:347-350). The jnp version runs ~30 eager
    device dispatches when called outside jit — ~30 device round
    trips per request. Parity with the jnp kernel is pinned
    by tests/test_packing_golden.py::test_efficiency_np_parity.

    O(entries), not O(nodes): the means only read the driver/executor
    entry rows, so everything is computed on the <= emax+1 gathered rows
    (full [N, 3] temporaries per admitted request were a measured serving
    hotspot at 10k nodes)."""
    import numpy as np

    executor_nodes = np.asarray(executor_nodes)
    entries = np.concatenate([[driver_node], executor_nodes])
    valid = entries >= 0
    if not valid.any():
        return AvgEfficiency(cpu=0.0, memory=0.0, gpu=0.0, max=0.0)
    schedulable = np.asarray(schedulable)
    available = np.asarray(available)
    dreq = np.asarray(driver_req)
    ereq = np.asarray(exec_req)
    idx = np.clip(entries, 0, None).astype(np.int64)
    uniq, pos = np.unique(idx, return_inverse=True)  # entry -> uniq row
    sched_u = schedulable[uniq]
    new_res_u = np.zeros_like(sched_u)
    if driver_node >= 0:
        new_res_u[pos[0]] += dreq
    ex_valid = valid.copy()
    ex_valid[0] = False
    if ex_valid.any():
        np.add.at(new_res_u, pos[ex_valid], ereq)
    reserved_u = (sched_u - available[uniq]) + new_res_u
    denom_u = np.where(sched_u == 0, 1, sched_u).astype(np.float32)
    eff_u = reserved_u.astype(np.float32) / denom_u
    gpu_node_u = sched_u[:, GPU_DIM] != 0
    eff_gpu_u = np.where(gpu_node_u, eff_u[:, GPU_DIM], 0.0)
    node_max_u = np.maximum(
        eff_gpu_u, np.maximum(eff_u[:, CPU_DIM], eff_u[:, MEM_DIM])
    )

    cnt = float(valid.sum())
    cpu_mean = float(np.where(valid, eff_u[pos, CPU_DIM], 0.0).sum() / cnt)
    mem_mean = float(np.where(valid, eff_u[pos, MEM_DIM], 0.0).sum() / cnt)
    gpu_valid = valid & gpu_node_u[pos]
    gpu_cnt = int(gpu_valid.sum())
    gpu_mean = (
        1.0  # no GPU nodes among entries => 1 (efficiency.go:139-144)
        if gpu_cnt == 0
        else float(np.where(gpu_valid, eff_gpu_u[pos], 0.0).sum() / gpu_cnt)
    )
    max_mean = float(np.where(valid, node_max_u[pos], 0.0).sum() / cnt)
    return AvgEfficiency(cpu=cpu_mean, memory=mem_mean, gpu=gpu_mean, max=max_mean)


def avg_packing_efficiency(
    cluster: ClusterTensors,
    driver_node: jnp.ndarray,
    executor_nodes: jnp.ndarray,
    driver_req: jnp.ndarray,
    exec_req: jnp.ndarray,
    *,
    include_executors_in_reserved: bool = True,
) -> AvgEfficiency:
    """`include_executors_in_reserved=False` reproduces a reference quirk:
    `minimalFragmentation` never writes executors into reservedResources
    (minimal_fragmentation.go:68-98, unlike pack_tightly.go:45-49 and
    distribute_evenly.go:58-60), so packing efficiencies — and therefore
    single-AZ zone selection — only see the driver's tentative reservation
    for that strategy. The ENTRIES averaged over are still driver + one per
    executor occurrence (single_az.go:84-97) in both modes."""
    return avg_packing_efficiency_arrays(
        cluster.schedulable,
        cluster.available,
        driver_node,
        executor_nodes,
        driver_req,
        exec_req,
        include_executors_in_reserved=include_executors_in_reserved,
    )


def avg_packing_efficiency_arrays(
    schedulable: jnp.ndarray,  # [N,3] i32
    available: jnp.ndarray,  # [N,3] i32 — CURRENT availability
    driver_node: jnp.ndarray,
    executor_nodes: jnp.ndarray,
    driver_req: jnp.ndarray,
    exec_req: jnp.ndarray,
    *,
    include_executors_in_reserved: bool = True,
) -> AvgEfficiency:
    """Array-based core of `avg_packing_efficiency`: callers that thread a
    mutated availability (the batched FIFO scan admits apps between zone
    scorings) pass it directly instead of rebuilding ClusterTensors."""
    new_res = new_reservation_tensor(
        schedulable.shape[0],
        driver_node,
        jnp.where(include_executors_in_reserved, executor_nodes, -1),
        driver_req,
        exec_req,
    )
    # schedulable - available = current reservation usage (efficiency.go:85-92).
    reserved_total = (schedulable - available) + new_res
    denom = jnp.where(schedulable == 0, 1, schedulable).astype(jnp.float32)
    eff = reserved_total.astype(jnp.float32) / denom  # [N,3]
    gpu_node = schedulable[:, GPU_DIM] != 0
    eff_gpu = jnp.where(gpu_node, eff[:, GPU_DIM], 0.0)
    node_max = jnp.maximum(eff_gpu, jnp.maximum(eff[:, CPU_DIM], eff[:, MEM_DIM]))

    entries = jnp.concatenate([driver_node[None], executor_nodes])
    valid = entries >= 0
    idx = jnp.clip(entries, 0)
    cnt = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)

    cpu_mean = jnp.sum(jnp.where(valid, eff[idx, CPU_DIM], 0.0)) / cnt
    mem_mean = jnp.sum(jnp.where(valid, eff[idx, MEM_DIM], 0.0)) / cnt
    gpu_valid = valid & gpu_node[idx]
    gpu_cnt = jnp.sum(gpu_valid)
    gpu_mean = jnp.where(
        gpu_cnt == 0,
        1.0,  # no GPU nodes among entries => 1 (efficiency.go:139-144)
        jnp.sum(jnp.where(gpu_valid, eff_gpu[idx], 0.0))
        / jnp.maximum(gpu_cnt, 1).astype(jnp.float32),
    )
    max_mean = jnp.sum(jnp.where(valid, node_max[idx], 0.0)) / cnt
    # Empty packing => worst efficiency (efficiency.go:44-52).
    none = jnp.sum(valid) == 0
    zero = jnp.float32(0.0)
    return AvgEfficiency(
        cpu=jnp.where(none, zero, cpu_mean),
        memory=jnp.where(none, zero, mem_mean),
        gpu=jnp.where(none, zero, gpu_mean),
        max=jnp.where(none, zero, max_mean),
    )
