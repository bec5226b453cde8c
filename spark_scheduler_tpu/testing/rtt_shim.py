"""Simulated-RTT device shim.

Every serving window pays a host-device round trip (its size on the
v5e chip is not measured yet; PERF.md), but CI and the dev box run on
local CPU, where every device boundary is microseconds and the fused
dispatch's amortization property (K windows per round trip) is
invisible. This shim makes it measurable WITHOUT hardware: installed into
the solver's device hook (core/solver.set_device_shim), it sleeps a
configurable share of the round trip at each boundary, on the thread that
would pay it on a real device:

  "h2d"      the dispatcher thread, once per device DISPATCH (window-batch
             upload + program launch RPC). This is the serialized cost a
             fused K-window batch pays once where K sequential dispatches
             pay it K times.
  "dispatch" a pool worker thread, once per pooled slot program launch
             (overlaps across slots, like the real per-device RPCs).
  "d2h"      the fetch-pool thread, once per decision-blob pull
             (concurrent pulls overlap, like concurrent device_get
             transfers).

Default split: h2d and d2h each take rtt_ms/2, dispatch takes 0 — one
unfused window costs one full round trip; a fused K-window dispatch costs
one round trip for all K. Event counts are recorded per kind, so tests
assert the amortization structurally (fused serving of K windows fires
ONE h2d and ONE d2h) rather than by wall clock.

`link_serialized=True` models a SHARED device link: every boundary's
sleep holds one link lock, so concurrent transfers from different
threads queue behind each other instead of overlapping. That is the
regime where the fleet's per-cluster round trips pile up (F windows = F
serialized RTTs) and the fused fleet dispatch's single launch pays once
— the stacked-vs-unstacked fleet bench runs BOTH arms under this mode so
the A/B measures launch fusion, not sleep overlap. Default False keeps
PR 19's overlapping-transfer semantics (independent per-device RPCs).
"""

from __future__ import annotations

import threading
import time


class SimulatedRTT:
    """Context-manager shim: `with SimulatedRTT(50.0) as rtt: ...` serves
    every window inside the block against a simulated 50 ms device round
    trip; `rtt.counts` holds the per-boundary event counts."""

    def __init__(
        self,
        rtt_ms: float = 50.0,
        *,
        h2d_ms: float | None = None,
        dispatch_ms: float = 0.0,
        d2h_ms: float | None = None,
        link_serialized: bool = False,
    ):
        half = rtt_ms / 2.0
        self.rtt_ms = rtt_ms
        self.h2d_ms = half if h2d_ms is None else h2d_ms
        self.dispatch_ms = dispatch_ms
        self.d2h_ms = half if d2h_ms is None else d2h_ms
        self.link_serialized = link_serialized
        self.counts = {"h2d": 0, "dispatch": 0, "d2h": 0}
        self._lock = threading.Lock()
        self._link = threading.Lock()
        self._prior = None
        self._installed = False

    def __call__(self, kind: str) -> None:
        with self._lock:
            if kind in self.counts:
                self.counts[kind] += 1
        ms = {
            "h2d": self.h2d_ms,
            "dispatch": self.dispatch_ms,
            "d2h": self.d2h_ms,
        }.get(kind, 0.0)
        if ms > 0:
            if self.link_serialized:
                # One shared link: this transfer occupies the link for
                # its full duration, queueing concurrent boundaries.
                with self._link:
                    time.sleep(ms / 1e3)
            else:
                time.sleep(ms / 1e3)

    def reset_counts(self) -> None:
        with self._lock:
            for k in self.counts:
                self.counts[k] = 0

    def install(self) -> "SimulatedRTT":
        from spark_scheduler_tpu.core import solver as _solver

        if self._installed:
            return self
        self._prior = _solver._DEVICE_SHIM
        _solver.set_device_shim(self)
        self._installed = True
        return self

    def uninstall(self) -> None:
        from spark_scheduler_tpu.core import solver as _solver

        if not self._installed:
            return
        _solver.set_device_shim(self._prior)
        self._prior = None
        self._installed = False

    def __enter__(self) -> "SimulatedRTT":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()
