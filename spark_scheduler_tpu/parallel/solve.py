"""Sharded batched FIFO admission.

Two composition levels over `ops.batched.batched_fifo_pack`:

  sharded_fifo_pack — one large cluster, node axis sharded over the mesh's
      "nodes" axis. The scan body's elementwise capacity math stays local to
      each shard; the total-capacity reduction, node sorts, and prefix sums
      become XLA collectives. This is the sequence-parallel analog for the
      10k-node axis (SURVEY.md §5.7).

  grouped_fifo_pack — G independent instance-group subproblems stacked on a
      leading axis, vmapped and sharded over "groups" (data parallel), each
      subproblem's node axis sharded over "nodes": full 2D parallelism.

Shardings are declared; collectives are XLA's to choose (no hand-written
ppermute/psum — scaling-book style).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.ops.batched import AppBatch, BatchedPacking, batched_fifo_pack


def node_sharding(mesh: Mesh, ndim: int, leading=()) -> NamedSharding:
    """THE sharding of a node-axis array on a ("nodes",) mesh: axis 0
    (after any `leading` axes) over "nodes", the rest replicated. The one
    definition both the one-shot sharded_fifo_pack placement and the
    serving engine's per-slot replica placement (core/solver.py
    _PoolSlot) build on — edit here, both follow."""
    spec = P(*leading, "nodes", *([None] * (ndim - 1 - len(leading))))
    return NamedSharding(mesh, spec)


def _shard_cluster(cluster: ClusterTensors, mesh: Mesh, leading=()) -> ClusterTensors:
    """Place cluster tensors with the node axis sharded over "nodes"."""

    def put(x):
        x = jnp.asarray(x)
        return jax.device_put(x, node_sharding(mesh, x.ndim, leading))

    return jax.tree_util.tree_map(put, cluster)


def _shard_apps(apps: AppBatch, mesh: Mesh, leading=()) -> AppBatch:
    """App batch: replicated across "nodes" (the scan walks it sequentially),
    optionally sharded on a leading "groups" axis. The optional per-app
    [B, N] masks carry a node axis, which shards over "nodes" like the
    cluster tensors."""

    def put(x, node_axis=False):
        if x is None:
            return None
        x = jnp.asarray(x)
        if node_axis:
            spec = P(*leading, None, "nodes")
        else:
            spec = P(*leading, *([None] * (x.ndim - len(leading))))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return AppBatch(
        driver_req=put(apps.driver_req),
        exec_req=put(apps.exec_req),
        exec_count=put(apps.exec_count),
        app_valid=put(apps.app_valid),
        skippable=put(apps.skippable),
        driver_cand=put(apps.driver_cand, node_axis=True),
        domain=put(apps.domain, node_axis=True),
        commit=put(apps.commit),
        reset=put(apps.reset),
    )


# Public surface for the serving window-solve engine (core/solver.py):
# `node_sharding` places a mesh slot's resident replica fields and
# `shard_apps` its window app batches with the SAME shardings the one-shot
# sharded_fifo_pack picks; the engine then runs its own blob-packing jit
# over them (computation follows input shardings — GSPMD).
def shard_apps(apps: AppBatch, mesh: Mesh) -> AppBatch:
    """App batch replicated over "nodes" except the per-app [B, N] masks,
    which shard their node axis with the cluster."""
    return _shard_apps(apps, mesh)


def sharded_fifo_pack(
    mesh: Mesh,
    cluster: ClusterTensors,
    apps: AppBatch,
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
) -> BatchedPacking:
    """Batched FIFO admission with the node axis sharded across the mesh.

    Node count must divide evenly by the "nodes" axis size (pad the cluster
    tensors with invalid slots — build_cluster_tensors' `pad_to`)."""
    n_shards = mesh.shape["nodes"]
    if cluster.available.shape[0] % n_shards:
        raise ValueError(
            f"node count {cluster.available.shape[0]} not divisible by "
            f'mesh "nodes" axis {n_shards}; pad with invalid slots'
        )
    cluster = _shard_cluster(cluster, mesh)
    apps = _shard_apps(apps, mesh)
    # Computation follows the input shardings (GSPMD); no explicit mesh
    # context needed — XLA partitions the scan body and inserts collectives.
    return batched_fifo_pack(cluster, apps, fill=fill, emax=emax, num_zones=num_zones)


def stack_groups(
    clusters: list[ClusterTensors], app_batches: list[AppBatch]
) -> tuple[ClusterTensors, AppBatch]:
    """Stack per-instance-group subproblems on a leading axis. All groups
    must be padded to identical (N, B, Emax) shapes (bucketing keeps the
    compile cache warm anyway)."""
    cluster = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *clusters
    )
    stacked_cols = []
    for field, cols in zip(AppBatch._fields, zip(*app_batches)):
        present = [x is not None for x in cols]
        if not any(present):
            stacked_cols.append(None)
            continue
        if not all(present):
            raise ValueError(
                f"AppBatch field {field!r} set for some groups but not others; "
                "masks must be provided for every group or none"
            )
        stacked_cols.append(np.stack([np.asarray(x) for x in cols]))
    return cluster, AppBatch(*stacked_cols)


def grouped_fifo_pack(
    mesh: Mesh,
    clusters: ClusterTensors,  # leaves stacked [G, N, ...]
    apps: AppBatch,  # leaves stacked [G, B, ...]
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
) -> BatchedPacking:
    """2D-parallel admission: vmap over the instance-group axis (sharded
    over "groups"), node axis of each subproblem sharded over "nodes"."""
    g = clusters.available.shape[0]
    if g % mesh.shape["groups"]:
        raise ValueError(
            f'group count {g} not divisible by mesh "groups" axis '
            f"{mesh.shape['groups']}; pad with empty groups"
        )
    clusters = _shard_cluster(clusters, mesh, leading=("groups",))
    apps = _shard_apps(apps, mesh, leading=("groups",))
    # unroll=1: scan unrolling regresses ~2x under vmap (measured on v5e —
    # the unrolled fused body blows the per-group working set).
    fn = jax.vmap(
        partial(
            batched_fifo_pack, fill=fill, emax=emax, num_zones=num_zones, unroll=1
        )
    )
    return fn(clusters, apps)
