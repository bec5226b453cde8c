"""The Mosaic window kernel compiled by the TPU's compiler for a described
v5e.

Nothing runs: each case lowers and compiles the kernel for a chip that is
described, not attached, and checks that the Mosaic custom call is in the
program. 4,096 nodes is the smallest node count on the 8-sublane layout
(ops/pallas_window.py `_SUBLANE_FOLD_MIN_NODES`). The served 10k and 100k
node buckets compile in ~30 s and ~140 s, too slow for this suite;
chip_smoke.py runs them on the chip.
"""

from __future__ import annotations

import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.ops.pallas_window import (
    segmented_window_from_flat,
    window_pack_pallas,
)

N_NODES = 4096
EMAX = 8
NUM_ZONES = 4
STRATEGIES = ("tightly-pack", "single-az-tightly-pack")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu would otherwise write its logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=sharding
        ),
        tree,
    )


def _cluster(n):
    i32 = np.zeros(n, np.int32)
    b = np.zeros(n, bool)
    return ClusterTensors(
        np.zeros((n, 3), np.int32), np.zeros((n, 3), np.int32),
        i32, i32, i32, i32, b, b, b,
    )


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


# Served window buckets (core/solver.py _build_segmented_window), as
# (segments, rows): `window`, the smallest; `queue`, a FIFO backlog's one
# request with up to 256 rows in its segment, the largest row bucket the
# benchmark's backlogs reach.
SHAPES = {"window": (4, 16), "queue": (4, 256)}


@pytest.mark.parametrize("fill", STRATEGIES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_window_kernel_compiles_for_v5e(one_chip, shape, fill):
    pad_segments, pad_rows = SHAPES[shape]
    win, _, _ = segmented_window_from_flat(
        np.zeros((1, 3), np.int32), np.zeros((1, 3), np.int32),
        np.zeros(1, np.int32), np.zeros(1, bool), [1],
        [np.zeros(N_NODES, bool)], [np.zeros(N_NODES, bool)],
        pad_segments=pad_segments, pad_rows=pad_rows,
    )
    lowered = window_pack_pallas.lower(
        _shapes(_cluster(N_NODES), one_chip), _shapes(win, one_chip),
        fill=fill, emax=EMAX, num_zones=NUM_ZONES,
    )
    _assert_mosaic(lowered)
