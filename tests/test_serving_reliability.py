"""Serving reliability layer through ContinuousBatchingServer and
BatchScheduler: deadlines, load shedding, supervised serve loop with
retry/backoff + circuit breaker, health states + /healthz, graceful
drain, and the satellite regressions (cancel notify, fire-all
callbacks, scheduler close with a wedged runner).

Runs on the StubModel double (tests/_serving_stub.py): no transformer
compiles, closed-form expected tokens, and FakeClock-driven deadlines —
fast enough for tier-1."""
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.serving import BatchScheduler, serve_metrics
from paddle_tpu.reliability import (CallbackError, CircuitBreaker,
                                    CircuitOpenError, DeadlineExceeded,
                                    FaultInjector, QueueFullError,
                                    RequestCancelled, RetryPolicy,
                                    SchedulerClosed, ServerClosed, faults)
from paddle_tpu.telemetry import FakeClock


def _prompt(*toks):
    return np.asarray(toks, np.int32)


def _srv(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 64)
    return ContinuousBatchingServer(StubModel(), **kw)


def _fast_retry():
    return RetryPolicy(base_delay_s=0.0, jitter=0.0)


def _until_queue_drains(srv, timeout=10.0):
    """Block until the serve thread has admitted everything queued —
    the deterministic way to build "slot busy, queue empty" fixtures."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with srv._lock:
            if not srv._queue:
                return
        time.sleep(0.005)
    raise AssertionError("queue never drained into slots")


# ---------------------------------------------------------- deadlines

class TestDeadlines:
    def test_expired_in_queue_fails_before_prefill(self):
        fc = FakeClock()
        srv = _srv(max_slots=1, clock=fc)
        ra = srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        rb = srv.submit(_prompt(4, 5), max_new_tokens=4, deadline_s=5.0)
        fc.advance(10.0)                 # rb expires while still queued
        outs = srv.run()
        np.testing.assert_array_equal(outs[ra],
                                      stub_tokens(_prompt(1, 2, 3), 4))
        assert rb not in outs
        assert isinstance(srv.failures[rb], DeadlineExceeded)
        # the expired request never cost a prefill (only ra's 3 tokens)
        assert srv.stats["prefill_tokens"] == 3

    def test_mid_decode_expiry_records_partial(self):
        fc = FakeClock()
        srv = _srv(max_slots=1, clock=fc)
        p = _prompt(2, 7)
        rid = srv.submit(p, max_new_tokens=10, deadline_s=5.0)
        srv.step()                        # admit + 1 decode: 2 tokens
        srv.step()                        # 3 tokens
        fc.advance(6.0)
        srv.step()                        # expiry sweep cancels the slot
        outs = srv.run()
        np.testing.assert_array_equal(outs[rid], stub_tokens(p, 10)[:3])

    def test_submit_with_spent_deadline_rejected(self):
        srv = _srv()
        with pytest.raises(DeadlineExceeded):
            srv.submit(_prompt(1), max_new_tokens=2, deadline_s=0.0)

    def test_paged_expiry_frees_pages(self):
        fc = FakeClock()
        srv = _srv(max_slots=2, cache_backend="paged", page_size=8,
                   clock=fc)
        rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=8,
                         deadline_s=1.0)
        srv.step()
        assert srv.pool_balance()[1] > 0          # pages live
        fc.advance(2.0)
        srv.step()
        srv.run()
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0 and pinned == 0
        assert rid is not None


# ----------------------------------------------------- load shedding

class TestLoadShedding:
    def test_reject_policy_raises_queue_full(self):
        srv = _srv(max_slots=1, max_queue=2)
        rids = [srv.submit(_prompt(i + 1), max_new_tokens=2)
                for i in range(2)]        # both queued (no step yet)
        with pytest.raises(QueueFullError, match="resubmit"):
            srv.submit(_prompt(9), max_new_tokens=2)
        outs = srv.run()                  # accepted requests unharmed
        assert set(outs) == set(rids)

    def test_evict_oldest_fails_oldest_accepts_new(self):
        srv = _srv(max_slots=1, max_queue=2, shed_policy="evict_oldest")
        old = srv.submit(_prompt(1), max_new_tokens=2)
        mid = srv.submit(_prompt(2), max_new_tokens=2)
        new = srv.submit(_prompt(3), max_new_tokens=2)   # evicts `old`
        outs = srv.run()
        assert old not in outs and {mid, new} <= set(outs)
        assert isinstance(srv.failures[old], QueueFullError)

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="shed_policy"):
            _srv(shed_policy="drop_newest")

    def test_max_queue_zero_evict_policy_falls_back_to_reject(self):
        """Review regression: evict_oldest with nobody to evict
        (max_queue=0) must shed typed, not IndexError."""
        srv = _srv(max_queue=0, shed_policy="evict_oldest")
        with pytest.raises(QueueFullError):
            srv.submit(_prompt(1), max_new_tokens=2)


# ------------------------------------------------- supervised serving

class TestSupervisedLoop:
    def test_tick_fault_retries_in_flight_survive(self):
        """Acceptance: a tick exception no longer kills the serve
        thread — other slots finish and new submits are served without
        a restart."""
        fi = FaultInjector().on(faults.DECODE_TICK, schedule=[1])
        srv = _srv(retry_policy=_fast_retry(), fault_injector=fi,
                   telemetry=True).start()
        a, b = _prompt(1, 2, 3), _prompt(4, 5)
        ra = srv.submit(a, max_new_tokens=6)
        rb = srv.submit(b, max_new_tokens=6)
        np.testing.assert_array_equal(srv.wait(ra, timeout=60),
                                      stub_tokens(a, 6))
        np.testing.assert_array_equal(srv.wait(rb, timeout=60),
                                      stub_tokens(b, 6))
        assert fi.fired(faults.DECODE_TICK) == 1    # the fault DID fire
        # new submit on the same (never restarted) thread
        c = _prompt(7, 8)
        rc = srv.submit(c, max_new_tokens=3)
        np.testing.assert_array_equal(srv.wait(rc, timeout=60),
                                      stub_tokens(c, 3))
        assert srv.health == "healthy"
        m = srv.telemetry.registry.get("server_tick_retries_total")
        assert m.value == 1.0
        srv.stop()

    def test_injected_prefill_fault_fails_one_request_only(self):
        fi = FaultInjector().on(faults.PREFILL, schedule=[0])
        srv = _srv(max_slots=1, retry_policy=_fast_retry(),
                   fault_injector=fi).start()
        a, b = _prompt(1, 2), _prompt(3, 4)
        ra = srv.submit(a, max_new_tokens=4)   # first admission dies
        rb = srv.submit(b, max_new_tokens=4)
        with pytest.raises(Exception, match="injected fault"):
            srv.wait(ra, timeout=60)
        np.testing.assert_array_equal(srv.wait(rb, timeout=60),
                                      stub_tokens(b, 4))
        srv.stop()

    def test_breaker_opens_unblocks_waiters_then_recovers(self):
        fcb = FakeClock()
        fi = FaultInjector().on(faults.DECODE_TICK,
                                schedule=range(0, 1000))
        srv = _srv(retry_policy=_fast_retry(),
                   breaker=CircuitBreaker(failure_threshold=3,
                                          reset_after_s=10.0, clock=fcb),
                   fault_injector=fi, telemetry=True).start()
        rid = srv.submit(_prompt(1, 2), max_new_tokens=4)
        with pytest.raises(CircuitOpenError, match="circuit breaker"):
            srv.wait(rid, timeout=60)
        assert srv.health == "degraded"
        # heal the engine, let the cooldown elapse -> half-open probe
        fi.disarm()
        fcb.advance(11.0)
        p = _prompt(5, 6)
        rid2 = srv.submit(p, max_new_tokens=4)   # degraded still accepts
        np.testing.assert_array_equal(srv.wait(rid2, timeout=60),
                                      stub_tokens(p, 4))
        assert srv.health == "healthy"           # probe closed the loop
        reg = srv.telemetry.registry
        assert reg.get("server_breaker_open_total").value == 1.0
        assert reg.get("server_health").value == 0.0
        srv.stop()

    def test_idle_degraded_server_recovers_without_traffic(self):
        """Review regression: after a breaker trip empties the server,
        the cooldown must still close the breaker and clear `degraded`
        — an idle server must not alert forever."""
        fcb = FakeClock()
        fi = FaultInjector().on(faults.DECODE_TICK, schedule=range(3))
        srv = _srv(retry_policy=_fast_retry(),
                   breaker=CircuitBreaker(failure_threshold=3,
                                          reset_after_s=5.0, clock=fcb),
                   fault_injector=fi).start()
        rid = srv.submit(_prompt(1), max_new_tokens=4)
        with pytest.raises(CircuitOpenError):
            srv.wait(rid, timeout=60)
        assert srv.health == "degraded"
        fcb.advance(6.0)                  # cooldown elapses; NO traffic
        deadline = time.monotonic() + 10
        while srv.health != "healthy" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.health == "healthy"
        srv.stop()

    def test_deadline_enforced_during_breaker_cooldown(self):
        """Review regression: a queued request's deadline must fire
        even while the open breaker gates ticks."""
        fcb = FakeClock()                  # never advanced: cooldown
        fi = FaultInjector().on(faults.DECODE_TICK, schedule=range(3))
        srv = _srv(retry_policy=_fast_retry(),
                   breaker=CircuitBreaker(failure_threshold=3,
                                          reset_after_s=1e9, clock=fcb),
                   fault_injector=fi).start()
        rid = srv.submit(_prompt(1), max_new_tokens=4)
        with pytest.raises(CircuitOpenError):
            srv.wait(rid, timeout=60)      # breaker now open, stays open
        rid2 = srv.submit(_prompt(2), max_new_tokens=4, deadline_s=0.2)
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            srv.wait(rid2, timeout=30)
        assert time.monotonic() - t0 < 10   # not the wait() timeout
        srv.stop()

    def test_final_chunk_callback_error_no_phantom_failure(self):
        """Review regression: budget=1 finishes at admission, so the
        poisoned callback fires AFTER harvest — the recorded result must
        stand and no phantom `failures` entry may accumulate."""
        srv = _srv(max_slots=1).start()
        rid = srv.submit(_prompt(5), max_new_tokens=1,
                         on_token=lambda r, t: 1 / 0)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with srv._lock:
                if rid in srv._results:
                    break
            time.sleep(0.005)
        time.sleep(0.1)          # let the callback-error handler run
        assert rid not in srv.failures
        np.testing.assert_array_equal(srv.wait(rid, timeout=10),
                                      stub_tokens(_prompt(5), 1))
        srv.stop()

    def test_breaker_open_drops_stale_stream_chunks(self):
        """Regression: no chunk of a request that failed with
        CircuitOpenError fires after recovery. Its first token was
        handed over before the decode that kept failing; nothing
        after it reaches the stream."""
        fcb = FakeClock()
        fi = FaultInjector().on(faults.DECODE_TICK, schedule=[0, 1, 2])
        seen = []
        srv = _srv(retry_policy=_fast_retry(),
                   breaker=CircuitBreaker(failure_threshold=3,
                                          reset_after_s=5.0, clock=fcb),
                   fault_injector=fi).start()
        rid = srv.submit(_prompt(1, 2), max_new_tokens=4,
                         on_token=lambda r, t: seen.append(r))
        with pytest.raises(CircuitOpenError):
            srv.wait(rid, timeout=60)
        fcb.advance(6.0)                       # cooldown -> probe OK
        p = _prompt(3, 4)
        rid2 = srv.submit(p, max_new_tokens=3,
                          on_token=lambda r, t: seen.append(r))
        np.testing.assert_array_equal(srv.wait(rid2, timeout=60),
                                      stub_tokens(p, 3))
        assert seen.count(rid) == 1, \
            "stale chunk for a failed request fired"
        assert rid2 in seen
        srv.stop()

    def test_breaker_open_drops_chunks_queued_by_failing_ticks(self):
        """Regression: a chunk a failing tick queued before it
        raised (here: one for the live request, queued at the decode
        fault point) is dropped when the breaker opens — it never fires
        on the recovered server."""
        fcb = FakeClock()
        seen = []
        on_token = lambda r, t: seen.append((r, t.tolist()))  # noqa: E731

        def fault():
            # runs inside the failing tick, under the server's lock
            st = next(s for s in srv._slots if s is not None)
            srv._deferred_cbs.append(
                (on_token, st.rid, np.asarray([-7], np.int32)))
            return faults.InjectedFault(faults.DECODE_TICK, -1)

        fi = FaultInjector().on(faults.DECODE_TICK, schedule=[0, 1, 2],
                                error=fault)
        srv = _srv(retry_policy=_fast_retry(),
                   breaker=CircuitBreaker(failure_threshold=3,
                                          reset_after_s=5.0, clock=fcb),
                   fault_injector=fi).start()
        rid = srv.submit(_prompt(1, 2), max_new_tokens=4,
                         on_token=on_token)
        with pytest.raises(CircuitOpenError):
            srv.wait(rid, timeout=60)
        fcb.advance(6.0)                       # cooldown -> probe OK
        p = _prompt(3, 4)
        rid2 = srv.submit(p, max_new_tokens=3, on_token=on_token)
        np.testing.assert_array_equal(srv.wait(rid2, timeout=60),
                                      stub_tokens(p, 3))
        srv.stop()
        assert fi.fired(faults.DECODE_TICK) == 3
        assert [t for r, t in seen if r == rid] == [
            stub_tokens(_prompt(1, 2), 1).tolist()], \
            "a chunk queued by a failed tick fired after recovery"
        assert any(r == rid2 for r, _ in seen)

    def test_wait_raises_typed_errors_directly(self):
        srv = _srv(max_slots=1, max_queue=1, max_cache_len=8192,
                   shed_policy="evict_oldest").start()
        # wedge the slot with a long request so the queue backs up
        long_rid = srv.submit(_prompt(1), max_new_tokens=5000,
                              deadline_s=None)
        _until_queue_drains(srv)
        old = srv.submit(_prompt(2), max_new_tokens=2)
        srv.submit(_prompt(3), max_new_tokens=2)       # evicts `old`
        with pytest.raises(QueueFullError):
            srv.wait(old, timeout=10)
        srv.cancel(long_rid)
        srv.stop()


# ------------------------------------------------- health and drain

class TestHealthAndDrain:
    def test_drain_finishes_queue_then_dies(self):
        srv = _srv(max_slots=1).start()
        a, b = _prompt(1, 2), _prompt(3)
        ra = srv.submit(a, max_new_tokens=5)
        rb = srv.submit(b, max_new_tokens=5)
        srv.stop(drain=True)
        assert srv.health == "dead"
        with pytest.raises(ServerClosed):
            srv.submit(_prompt(9), max_new_tokens=2)
        # results were flushed, waiters can still collect
        np.testing.assert_array_equal(srv.wait(ra, timeout=5),
                                      stub_tokens(a, 5))
        np.testing.assert_array_equal(srv.wait(rb, timeout=5),
                                      stub_tokens(b, 5))

    def test_hard_stop_fails_queued_flushes_partials(self):
        srv = _srv(max_slots=1, max_cache_len=8192).start()
        ra = srv.submit(_prompt(1), max_new_tokens=5000)  # never finishes
        _until_queue_drains(srv)                          # ra holds the slot
        rb = srv.submit(_prompt(2), max_new_tokens=2)     # stuck queued
        srv.stop()
        out = srv.wait(ra, timeout=5)                     # partial flush
        assert 1 <= len(out) < 5000
        np.testing.assert_array_equal(
            out, stub_tokens(_prompt(1), 5000)[:len(out)])
        with pytest.raises(ServerClosed):
            srv.wait(rb, timeout=5)

    def test_submit_racing_drain_rejected_typed_healthz_503(self):
        """ISSUE 7 satellite: a submit() racing stop(drain=True) must
        be rejected TYPED (ServerClosed — never silently dropped, never
        admitted into a dying server), and /healthz must answer 503 for
        the whole drain window (draining) and after it (dead). The
        in-flight request pins the drain open via a gated on_token
        callback, so the window is deterministic, not a sleep race."""
        srv = _srv(max_slots=1, telemetry=True).start()
        ms = serve_metrics(srv)
        entered, release = threading.Event(), threading.Event()

        def gate(rid, toks):
            entered.set()
            assert release.wait(timeout=30)

        p = _prompt(1, 2, 3)
        rid = srv.submit(p, max_new_tokens=8, on_token=gate)
        assert entered.wait(timeout=30)     # mid-decode, stream gated
        t = threading.Thread(target=lambda: srv.stop(drain=True,
                                                     timeout=60))
        t.start()
        try:
            deadline = time.monotonic() + 30
            while srv.health != "draining":
                assert time.monotonic() < deadline, "never saw draining"
                time.sleep(0.002)
            # the drain window is OPEN (in-flight request gated):
            # admission must refuse typed...
            with pytest.raises(ServerClosed):
                srv.submit(_prompt(9), max_new_tokens=2)
            # ...and the readiness probe must already say 503
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(ms.url + "/healthz")
            assert ei.value.code == 503
            assert b'"draining"' in ei.value.read()
        finally:
            release.set()
            t.join(timeout=60)
        assert not t.is_alive()
        # drained, not dropped: the in-flight request completed in full
        np.testing.assert_array_equal(srv.wait(rid, timeout=5),
                                      stub_tokens(p, 8))
        # after the drain the server is dead — still 503, same verdict
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(ms.url + "/healthz")
        assert ei.value.code == 503
        ms.close()

    def test_queued_requests_complete_during_drain_race(self):
        """Admissions QUEUED before the drain began are not shed by it:
        stop(drain=True) completes them; only post-drain submits see
        ServerClosed."""
        srv = _srv(max_slots=1).start()
        entered, release = threading.Event(), threading.Event()

        def gate(rid, toks):
            entered.set()
            assert release.wait(timeout=30)

        a, b = _prompt(1, 2), _prompt(3, 4)
        ra = srv.submit(a, max_new_tokens=4, on_token=gate)
        assert entered.wait(timeout=30)
        rb = srv.submit(b, max_new_tokens=4)    # queued behind ra
        stopper = threading.Thread(
            target=lambda: srv.stop(drain=True, timeout=60))
        stopper.start()
        deadline = time.monotonic() + 30
        while srv.health != "draining":
            assert time.monotonic() < deadline
            time.sleep(0.002)
        with pytest.raises(ServerClosed):
            srv.submit(_prompt(5), max_new_tokens=1)
        release.set()
        stopper.join(timeout=60)
        assert not stopper.is_alive()
        np.testing.assert_array_equal(srv.wait(ra, timeout=5),
                                      stub_tokens(a, 4))
        np.testing.assert_array_equal(srv.wait(rb, timeout=5),
                                      stub_tokens(b, 4))
        assert not srv.failures

    def test_restart_after_stop_resets_health(self):
        srv = _srv().start()
        srv.stop()
        assert srv.health == "dead"
        srv.start()
        assert srv.health == "healthy"
        p = _prompt(4, 4)
        rid = srv.submit(p, max_new_tokens=3)
        np.testing.assert_array_equal(srv.wait(rid, timeout=60),
                                      stub_tokens(p, 3))
        srv.stop()

    def test_healthz_and_reliability_metrics_exposed(self):
        srv = _srv(telemetry=True, max_queue=1, max_slots=1,
                   max_cache_len=8192).start()
        ms = serve_metrics(srv)
        try:
            with urllib.request.urlopen(ms.url + "/healthz") as r:
                assert r.status == 200
                assert b'"healthy"' in r.read()
            # trip a shed so the counter is nonzero in the exposition
            srv.submit(_prompt(1), max_new_tokens=4000)
            _until_queue_drains(srv)       # it holds the single slot
            srv.submit(_prompt(2), max_new_tokens=2)
            with pytest.raises(QueueFullError):
                srv.submit(_prompt(3), max_new_tokens=2)
            with urllib.request.urlopen(ms.url + "/metrics") as r:
                text = r.read().decode()
            for name in ("server_shed_total", "server_deadline_expired_total",
                         "server_tick_retries_total", "server_health"):
                assert name in text, name
            assert 'server_shed_total{policy="reject"} 1' in text
            srv.stop()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(ms.url + "/healthz")
            assert ei.value.code == 503
            assert b'"dead"' in ei.value.read()
        finally:
            ms.close()


# ------------------------------------------- satellite regressions

class TestSatelliteRegressions:
    def test_cancel_notifies_waiter_immediately(self):
        """Satellite 1: cancel() must notify _done_cv — a blocked
        wait() returns the partial NOW, not at the next 1 s poll."""
        srv = _srv(max_slots=1, max_cache_len=8192).start()
        rid = srv.submit(_prompt(3), max_new_tokens=5000)
        got = {}

        def waiter():
            t0 = time.monotonic()
            got["out"] = srv.wait(rid, timeout=30)
            got["dt"] = time.monotonic() - t0

        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.15)                    # waiter is parked in wait()
        assert srv.cancel(rid) is True
        th.join(timeout=10)
        assert "out" in got
        # well under the 1 s condition-poll fallback: the notify did it
        assert got["dt"] < 0.95
        np.testing.assert_array_equal(
            got["out"], stub_tokens(_prompt(3), 5000)[:len(got["out"])])
        srv.stop()

    def test_cancel_queued_raises_typed_error_in_wait(self):
        srv = _srv(max_slots=1, max_cache_len=8192).start()
        busy = srv.submit(_prompt(1), max_new_tokens=5000)
        _until_queue_drains(srv)
        rid = srv.submit(_prompt(2), max_new_tokens=2)
        assert srv.cancel(rid) is True
        with pytest.raises(RequestCancelled):
            srv.wait(rid, timeout=10)
        srv.cancel(busy)
        srv.stop()

    def test_fire_callbacks_fires_all_then_raises_first(self):
        """Satellite 2: one poisoned on_token must not eat the other
        requests' queued chunks — they fire, THEN the error surfaces."""
        good = []
        srv = _srv(max_slots=2, tick_block=2)
        # poisoned request admitted FIRST (slot 0, fires first)
        rb = srv.submit(_prompt(9, 9), max_new_tokens=6,
                        on_token=lambda r, t: 1 / 0)
        ra = srv.submit(_prompt(1, 2), max_new_tokens=6,
                        on_token=lambda r, t: good.append(t.copy()))
        with pytest.raises(CallbackError) as ei:
            srv.run()
        assert ei.value.rid == rb
        assert isinstance(ei.value.__cause__, ZeroDivisionError)
        assert good, "good request's chunk was dropped by the poisoned one"
        np.testing.assert_array_equal(
            np.concatenate(good)[:1], stub_tokens(_prompt(1, 2), 6)[:1])
        assert ra is not None

    def test_scheduler_close_fails_pending_on_wedged_runner(self):
        """Satellite 3: close() must not leave futures hanging when the
        runner wedges — they fail typed, and the timeout surfaces."""
        release = threading.Event()

        def runner(arrs):
            release.wait(30)
            return [arrs[0]]

        sched = BatchScheduler(runner, max_batch_size=1, max_delay_ms=1.0)
        f1 = sched.submit(np.ones((1, 2), np.float32))
        time.sleep(0.15)                   # worker is inside runner now
        f2 = sched.submit(np.ones((1, 2), np.float32))
        with pytest.raises(TimeoutError, match="did not exit"):
            sched.close(timeout=0.3)
        assert isinstance(f1.exception(timeout=5), SchedulerClosed)
        assert isinstance(f2.exception(timeout=5), SchedulerClosed)
        release.set()                      # unwedge; late result ignored

    def test_scheduler_queue_bound_and_deadline(self):
        release = threading.Event()

        def runner(arrs):
            release.wait(30)
            return [arrs[0]]

        sched = BatchScheduler(runner, max_batch_size=1, max_delay_ms=1.0,
                               max_queue=1)
        f1 = sched.submit(np.ones((1, 2), np.float32))
        time.sleep(0.15)                   # f1 in flight, queue empty
        f2 = sched.submit(np.ones((1, 2), np.float32))
        with pytest.raises(QueueFullError, match="max_queue"):
            sched.submit(np.ones((1, 2), np.float32))
        # a queued request whose deadline passes fails before launch
        f3 = None
        release.set()
        time.sleep(0.05)
        f3 = sched.submit(np.ones((1, 2), np.float32), deadline_s=0.0)
        assert isinstance(f3.exception(timeout=5), DeadlineExceeded)
        assert f1.result(timeout=5) is not None
        assert f2.result(timeout=5) is not None
        sched.close()

    def test_scheduler_submit_after_close_typed(self):
        sched = BatchScheduler(lambda s: [s[0]], max_batch_size=2)
        sched.close()
        with pytest.raises(SchedulerClosed, match="closed"):
            sched.submit(np.ones((1, 2), np.float32))
