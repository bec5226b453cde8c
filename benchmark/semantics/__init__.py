"""What a configuration means: its world, its load and its reference.

A configuration names its semantics with `"semantics": "<name>"` (absent:
`static_gang`), and the harness loads `semantics/<name>.py` by that name,
as it loads a per-layer metric's reader. A semantics module supplies

  build_world(config, traffic, seed, now) -> World
      the cluster and the apps on it, made from the seed; `now` is the
      run's start (seconds since the Unix epoch);
  make_generator(world, config, traffic, served, annotate)
      kube-scheduler and the Spark apps behind it, with LoadGen's
      interface: cycle(), run_until(deadline) -> end time, close(),
      calls, errors, log, bodies, client_s, watch_s, in_window, and
      expected_reservations() -> {app: (driver node, executor nodes)},
      which the program's invariants are held to;
  replay(world, log, bodies) -> dict
      the run's answers against the plain reference: the counts behind
      the harness's checks (`driver_mismatches`, `executor_mismatches`,
      `denials_bad_map`, `reference_overcommitted_nodes`) and, under
      "checks", any further named counts, each held to 0;

and may name, in `SPANS`, program spans beyond spans.PROGRAM_SPANS that
its traced runs should read.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class World:
    """What a run starts from. The harness boots the program on `nodes`,
    `pods` and `apps` and then drops them; the rest is the semantics
    module's own."""

    nodes: list[dict]  # Node JSON
    pods: list[dict]  # Pod JSON: running apps' bound pods, pending drivers
    apps: list[tuple[str, str, list[str], list[str]]]  # see served.Served
    n_nodes: int
    executors: int  # executors a window row places, for the readers
    running: list  # running apps, oldest first
    pending: list  # pending drivers in creation order
    own: dict[str, Any] = dataclasses.field(default_factory=dict)
