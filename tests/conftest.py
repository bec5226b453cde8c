"""Test env: pin CPU with an 8-device virtual mesh
(`xla_force_host_platform_device_count=8`) so device-sharding tests run
without TPU hardware, and keep the persistent compilation cache off (the
server bootstrap points it at the repo's .jax_cache; tests compile in
process). The chip is exercised by `python chip_smoke.py`, not here."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

# SPARK_SCHEDULER_TEST_INGEST=native runs every server-constructing suite on
# the native ingest lane (the CI `ingest-native` job leg): tests that do not
# pass an explicit `ingest=` inherit the override, so the whole parametrized
# server matrix re-runs against the C++ framer/decoder without duplicating
# the suites. Tests pinning a specific lane still win (explicit kwarg).
_TEST_INGEST = os.environ.get("SPARK_SCHEDULER_TEST_INGEST")
if _TEST_INGEST:
    import spark_scheduler_tpu.server.http as _http_mod

    _orig_server_init = _http_mod.SchedulerHTTPServer.__init__

    def _ingest_forcing_init(self, *args, **kwargs):
        kwargs.setdefault("ingest", _TEST_INGEST)
        _orig_server_init(self, *args, **kwargs)

    _http_mod.SchedulerHTTPServer.__init__ = _ingest_forcing_init

# SPARK_SCHEDULER_TEST_PRUNE=<k> runs every solver-constructing suite with
# sound top-K candidate pruning enabled (the CI `prune` job leg): solvers
# that do not pin an explicit `prune_top_k` inherit the override, so the
# solver/extender equivalence suites and the chaos-matrix soak re-run with
# the two-tier solve live — pruning cannot silently regress decision
# equality or the fault paths. Tests pinning prune_top_k (including the
# unpruned baselines inside tests/test_prune_equivalence.py, which pass 0)
# still win.
_TEST_PRUNE = os.environ.get("SPARK_SCHEDULER_TEST_PRUNE")
if _TEST_PRUNE and int(_TEST_PRUNE) > 0:  # "0" must mean OFF, not k=8
    from spark_scheduler_tpu.core import solver as _solver_mod

    _orig_solver_init = _solver_mod.PlacementSolver.__init__
    _prune_k = int(_TEST_PRUNE) if int(_TEST_PRUNE) > 1 else 8

    def _prune_forcing_init(self, *args, **kwargs):
        kwargs.setdefault("prune_top_k", _prune_k)
        _orig_solver_init(self, *args, **kwargs)

    _solver_mod.PlacementSolver.__init__ = _prune_forcing_init


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound the process's virtual-memory map count across the suite.

    Every XLA:CPU compile JIT-loads code pages as a handful of mmap
    regions, and compiled executables are cached for the life of the
    process — a full run accumulates tens of thousands of mappings and
    crosses the kernel's default vm.max_map_count (65530), at which point
    the next compile's mmap fails and XLA segfaults (observed at ~62k maps,
    deterministically in whichever test compiles next — historically the
    8-device sharded window test). Dropping the executable caches at module
    boundaries keeps the count bounded; cross-module recompiles are cheap
    next to the suite's own per-module compiles."""
    yield
    jax.clear_caches()
