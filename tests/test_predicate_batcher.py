"""PredicateBatcher contracts the serving transports lean on: timeout
shedding racing the dispatcher's claim, the claim_log hard bound, and the
callback-mode (submit_nowait) completion path the async transport uses.

All tests drive a stub extender — host-only, no solver — so the races can
be staged deterministically with events.
"""

import sys
import threading
import time

import pytest

from spark_scheduler_tpu.server.http import PredicateBatcher


class StubTicket:
    def __init__(self, batch_len):
        self.handle = None  # solo/sync path: complete immediately
        self.batch_len = batch_len


class StubExtender:
    """Synchronous stub: every window completes inline. `stall` (when set)
    blocks the dispatcher inside dispatch — after the claim, before
    completion — which is exactly the window the timeout race needs."""

    def __init__(self):
        self.stall = None  # threading.Event the dispatcher waits on
        self.dispatched = 0
        self.completed = 0

    def predicate_window_dispatch(self, args_list):
        self.dispatched += len(args_list)
        if self.stall is not None:
            assert self.stall.wait(10), "test stall never released"
        return StubTicket(len(args_list))

    def predicate_window_complete(self, ticket):
        self.completed += ticket.batch_len
        return ["ok"] * ticket.batch_len


def test_claim_log_is_hard_bounded():
    """The claim log must stop recording at CLAIM_LOG_CAP — a long soak
    cannot grow it unbounded (it is a forensic tail, not a history)."""
    ext = StubExtender()
    # max_window=1: every request is its own claim, so the log would reach
    # n entries without the bound.
    b = PredicateBatcher(ext, max_window=1, hold_ms=0)
    cap = PredicateBatcher.CLAIM_LOG_CAP
    n = cap + 150
    try:
        done = threading.Semaphore(0)
        errs = []

        def client(k):
            try:
                for _ in range(k):
                    assert b.submit("x", timeout=10) == "ok"
            except Exception as exc:  # pragma: no cover - surfaced below
                errs.append(exc)
            finally:
                done.release()

        n_threads = 8
        per = n // n_threads + 1
        for _ in range(n_threads):
            threading.Thread(target=client, args=(per,), daemon=True).start()
        for _ in range(n_threads):
            assert done.acquire(timeout=60)
        assert not errs, errs
        # Enough windows ran to cross the bound, and recording stopped
        # EXACTLY at it.
        assert b.windows_served > cap
        assert len(b.claim_log) == cap, len(b.claim_log)
    finally:
        b.stop()


def test_timeout_race_with_claimed_entry_completes_once_and_prunes():
    """A request that times out in submit() AFTER the dispatcher claimed
    its entry: the solve proceeds, the entry completes exactly once, and
    its slot does NOT linger in _claimed (regression: the lazy rebuild
    only ran on the next claim — on an idle server, never)."""
    ext = StubExtender()
    ext.stall = threading.Event()
    b = PredicateBatcher(ext, max_window=4, hold_ms=0)
    try:
        with pytest.raises(TimeoutError):
            b.submit("slow", timeout=0.15)
        # The dispatcher is stalled INSIDE dispatch — the entry was claimed,
        # so the timed-out submit couldn't remove it from the queue.
        assert b.queue_depth() == 0
        with b._cv:
            assert len(b._claimed) == 1
        ext.stall.set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with b._cv:
                if not b._claimed and b.requests_served == 1:
                    break
            time.sleep(0.01)
        with b._cv:
            assert b._claimed == [], "completed entry left its slot in _claimed"
        assert b.requests_served == 1  # completed exactly once
        # The batcher is healthy: a fresh request round-trips.
        ext.stall = None
        assert b.submit("next", timeout=5) == "ok"
    finally:
        ext.stall = None
        b.stop()


def test_timeout_unclaimed_entry_is_removed_from_queue():
    """A request that times out BEFORE the dispatcher claims it is shed
    from the queue — no window slot is burned solving for a client that
    already got an error."""
    ext = StubExtender()
    ext.stall = threading.Event()
    b = PredicateBatcher(ext, max_window=1, hold_ms=0)
    try:
        # First request parks the dispatcher inside dispatch...
        t1 = threading.Thread(
            target=lambda: b.submit("first", timeout=10), daemon=True
        )
        t1.start()
        deadline = time.monotonic() + 5
        while ext.dispatched == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        # ...so the second request stays UNCLAIMED in the queue and its
        # timeout must remove it.
        with pytest.raises(TimeoutError):
            b.submit("second", timeout=0.1)
        assert b.queue_depth() == 0
        ext.stall.set()
        t1.join(5)
        # Only the first request was ever dispatched/served.
        deadline = time.monotonic() + 5
        while ext.completed < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)  # would-be second window would dispatch by now
        assert ext.dispatched == 1
        assert b.requests_served == 1
    finally:
        ext.stall = None
        b.stop()


def test_submit_nowait_completion_callback():
    """Callback-mode submission (the async transport's path): done fires
    exactly once from the dispatcher with the entry's result."""
    ext = StubExtender()
    b = PredicateBatcher(ext, max_window=4, hold_ms=0)
    try:
        fired = []
        done_evt = threading.Event()

        def done(result, exc):
            fired.append((result, exc))
            done_evt.set()

        b.submit_nowait("x", done)
        assert done_evt.wait(5)
        assert fired == [("ok", None)]
        with b._cv:
            assert b._claimed == []
    finally:
        b.stop()


def test_abandon_unclaimed_nowait_entry_never_fires():
    ext = StubExtender()
    ext.stall = threading.Event()
    b = PredicateBatcher(ext, max_window=1, hold_ms=0)
    try:
        blocker_done = threading.Event()
        b.submit_nowait("blocker", lambda r, e: blocker_done.set())
        deadline = time.monotonic() + 5
        while ext.dispatched == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        fired = []
        entry = b.submit_nowait("victim", lambda r, e: fired.append((r, e)))
        assert b.abandon(entry) is True  # unclaimed: removed
        assert b.abandon(entry) is False  # idempotent
        ext.stall.set()
        assert blocker_done.wait(5)
        time.sleep(0.05)
        assert fired == []  # abandoned entry's callback never fired
        assert ext.dispatched == 1
    finally:
        ext.stall = None
        b.stop()


def test_stop_fails_pending_nowait_entries_via_callback():
    """Shutdown must flush callback entries with the shutting-down error —
    the async transport's in-flight requests get their error response
    instead of hanging."""
    ext = StubExtender()
    ext.stall = threading.Event()
    b = PredicateBatcher(ext, max_window=1, hold_ms=0)
    fired = []
    evt = threading.Event()
    b.submit_nowait("stuck", lambda r, e: (fired.append((r, e)), evt.set()))
    deadline = time.monotonic() + 5
    while ext.dispatched == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    stopper = threading.Thread(target=b.stop, daemon=True)
    stopper.start()
    # stop() joins the (stalled) dispatcher with a timeout, then fails the
    # claimed entry; the late release must be harmless (idempotent set).
    assert evt.wait(15)
    assert fired and fired[0][0] is None
    assert isinstance(fired[0][1], RuntimeError)
    ext.stall.set()
    stopper.join(10)
    assert not stopper.is_alive()
    assert len(fired) == 1  # a late dispatcher set() never double-fires


def test_handoff_counters_rise_by_one_per_request():
    """Each blocking request adds exactly one hand-off, and its queue and
    wake legs only ever add non-negative seconds."""
    ext = StubExtender()
    b = PredicateBatcher(ext, max_window=4, hold_ms=0)
    try:
        prev = b.stats()
        assert (prev["handoffs"], prev["queue_wait_s"], prev["wake_wait_s"]) == (
            0, 0.0, 0.0
        )
        for _ in range(20):
            assert b.submit("x", timeout=5) == "ok"
            cur = b.stats()
            assert cur["handoffs"] == prev["handoffs"] + 1
            assert cur["queue_wait_s"] >= prev["queue_wait_s"] >= 0.0
            assert cur["wake_wait_s"] >= prev["wake_wait_s"] >= 0.0
            prev = cur
    finally:
        b.stop()


def test_handoff_counters_lose_no_update_under_concurrent_handlers():
    """Many handler threads finishing at once: the counters are summed
    under a lock, so the hand-off count is exact."""
    ext = StubExtender()
    b = PredicateBatcher(ext, max_window=4, hold_ms=0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        n_threads, per = 16, 25
        errs = []

        def client():
            try:
                for _ in range(per):
                    assert b.submit("x", timeout=10) == "ok"
            except Exception as exc:  # pragma: no cover - surfaced below
                errs.append(exc)

        threads = [threading.Thread(target=client) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        assert not errs, errs
        stats = b.stats()
        assert stats["handoffs"] == n_threads * per
        assert stats["queue_wait_s"] >= 0.0 and stats["wake_wait_s"] >= 0.0
    finally:
        sys.setswitchinterval(old)
        b.stop()
