"""Request-journey tracing, flight recorder, postmortem capture
(ISSUE 10).

Contracts under test:

- ``FlightRecorder``: bounded ring (oldest overwritten), kind filter,
  bounded postmortem store; DISABLED recorder performs zero clock
  reads and zero lock acquisitions (FakeClock + counting-lock
  asserted), and a server treats it exactly like None.
- per-tick dispatch profile: every non-empty tick publishes its
  host->device dispatch map to the recorder (``tick`` events), the
  ``serving_tick_dispatches`` histogram and
  ``server_dispatches_total{op}`` — the ROADMAP item-4 baseline.
- journeys: a request routed -> killed-replica failover -> requeued ->
  admitted -> preempted -> replayed -> finished yields ONE complete
  ``journey(rid)`` timeline across replicas and ONE connected flow in
  the merged fleet Perfetto export (acceptance scenario).
- postmortems: breaker open freezes the parked queue + pool balance +
  block-table occupancy; request failures and replica death capture
  bundles too; ``/debug/journey/<rid>`` + ``/debug/postmortem`` serve
  them.
- chaos determinism: same-seed fault storms produce identical recorder
  event sequences (timestamps aside); ``fault_fires_total{point}``
  makes storms visible on /metrics.
- PR-2 span timelines gain ``request.parked`` / ``request.replay``.

Everything runs on the StubModel double — tier-1 fast, no transformer
compiles."""
import importlib.util
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.router import ReplicaRouter
from paddle_tpu.inference.serving import serve_metrics
from paddle_tpu.reliability import (CircuitBreaker, CircuitOpenError,
                                    FaultInjector, RetryPolicy, faults)
from paddle_tpu.telemetry import (FakeClock, FlightRecorder, Journey,
                                  JourneyRecorder, MetricRegistry,
                                  ServerTelemetry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prompt(*toks):
    return np.asarray(toks, np.int32)


def _server(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 8)
    return ContinuousBatchingServer(StubModel(), **kw)


def _drive(srv, max_ticks=20_000, stop=None):
    """Single-threaded tolerant drive (chaos-suite pattern): step until
    idle, swallowing injected tick faults like the supervised loop
    would. ``stop`` (predicate) ends the drive early."""
    ticks = 0
    while True:
        with srv._lock:
            busy = srv._busy_locked()
        if not busy or (stop is not None and stop()):
            return
        try:
            srv.step()
        except Exception:
            pass
        ticks += 1
        assert ticks < max_ticks, "drive did not converge"


class _CountingLock:
    """Context-manager shim standing in for a threading.Lock so tests
    can assert the disabled path never acquires it."""

    def __init__(self):
        self.acquisitions = 0

    def __enter__(self):
        self.acquisitions += 1
        return self

    def __exit__(self, *exc):
        return False


# --------------------------------------------------------------------------
# FlightRecorder unit contracts
# --------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_bound_and_order(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("ev", i=i)
        evs = rec.events()
        assert len(rec) == 4 and rec.total == 10
        assert [e["i"] for e in evs] == [6, 7, 8, 9]
        assert [e["seq"] for e in evs] == [6, 7, 8, 9]

    def test_kind_filter_and_last(self):
        rec = FlightRecorder()
        for i in range(6):
            rec.record("a" if i % 2 else "b", i=i)
        assert [e["i"] for e in rec.events(kind="a")] == [1, 3, 5]
        assert [e["i"] for e in rec.events(kind="a", last=2)] == [3, 5]
        # unfiltered `last` copies only the window (postmortem capture
        # must pay O(keep_events), not O(capacity))
        assert [e["i"] for e in rec.events(last=2)] == [4, 5]

    def test_reserved_field_keys_degrade_not_crash(self):
        rec = FlightRecorder()
        rec.record("ev", kind="sneaky", t=99, seq=-1, ok=1)
        (e,) = rec.events()
        assert e["kind"] == "ev" and e["seq"] == 0 and e["ok"] == 1
        assert e["kind_"] == "sneaky" and e["t_"] == 99

    def test_postmortem_bundles_bounded_and_snapshot(self):
        rec = FlightRecorder(keep_events=3, max_postmortems=2)
        for i in range(5):
            rec.record("ev", i=i)
        b1 = rec.postmortem("first", pool={"free": 1})
        assert [e["i"] for e in b1["events"]] == [2, 3, 4]
        assert b1["pool"] == {"free": 1}
        rec.postmortem("second")
        rec.postmortem("third")
        reasons = [b["reason"] for b in rec.postmortems()]
        assert reasons == ["second", "third"]   # bounded, newest win

    def test_disabled_recorder_zero_clock_zero_locks(self):
        fc = FakeClock()
        rec = FlightRecorder(clock=fc, enabled=False)
        lock = _CountingLock()
        rec._lock = lock
        assert rec.record("ev", x=1) is None
        assert rec.postmortem("why") is None
        assert fc.reads == 0 and lock.acquisitions == 0
        assert rec.events() == [] or True   # events() may lock; state empty

    def test_server_treats_disabled_recorder_as_none(self):
        fc = FakeClock()
        rec = FlightRecorder(clock=fc, enabled=False)
        srv = _server(recorder=rec)
        assert srv._rec is None
        rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        out = srv.run()
        np.testing.assert_array_equal(out[rid], stub_tokens([1, 2, 3], 4))
        assert fc.reads == 0 and rec.events() == []
        assert srv.postmortems() == []


# --------------------------------------------------------------------------
# JourneyRecorder unit contracts
# --------------------------------------------------------------------------
class TestJourneyRecorder:
    def test_timeline_and_handles(self):
        fc = FakeClock()
        jr = JourneyRecorder(clock=fc)
        h = jr.begin("t1")
        h.event("submitted", rid=7)
        fc.advance(1.5)
        h.at("replica0").event("queued")
        tl = jr.journey("t1")
        assert [(e["phase"], e["where"]) for e in tl] == \
            [("submitted", "router"), ("queued", "replica0")]
        assert tl[1]["t"] - tl[0]["t"] == pytest.approx(1.5)
        assert jr.journey("nope") is None

    def test_reserved_field_keys_degrade_not_crash(self):
        """A field named like a reserved key ('where' collides with
        the handle's positional hop label) must degrade to a suffixed
        field — regression: deadline expiry once emitted
        event('expired', where=...) and TypeError'd the serve tick."""
        jr = JourneyRecorder()
        h = jr.begin("t1")
        h.event("expired", where="queued", phase="x", t=1)
        (e,) = jr.journey("t1")
        assert e["phase"] == "expired" and e["where"] == "router"
        assert e["where_"] == "queued" and e["phase_"] == "x"

    def test_deadline_expiry_with_journey_attached(self):
        """End-to-end regression for the same bug: a journeyed request
        expiring in queue AND one expiring mid-decode/parked must not
        kill the tick."""
        fc = FakeClock()
        jr = JourneyRecorder(clock=fc)
        srv = _server(clock=fc)
        h = jr.begin("rq")
        rid = srv.submit(_prompt(1, 2), max_new_tokens=4,
                         deadline_s=1.0, journey=h)
        fc.advance(2.0)
        srv.step()                       # expires in queue — must not raise
        assert rid in srv.failures
        phases = [(e["phase"], e.get("at")) for e in jr.journey("rq")]
        assert ("expired", "queued") in phases

    def test_eviction_drops_oldest_whole(self):
        jr = JourneyRecorder(max_journeys=2)
        for i in range(3):
            jr.begin(f"t{i}").event("submitted")
        assert jr.journey("t0") is None and jr.dropped == 1
        assert jr.journey("t2") is not None
        # events for an evicted tid are dropped silently
        Journey(jr, "t0", "router").event("late")
        assert jr.journey("t0") is None

    def test_disabled_zero_clock_zero_locks(self):
        fc = FakeClock()
        jr = JourneyRecorder(clock=fc, enabled=False)
        lock = _CountingLock()
        jr._lock = lock
        h = jr.begin("t1")
        h.event("submitted")
        assert fc.reads == 0 and lock.acquisitions == 0

    def test_router_treats_disabled_journeys_as_none(self):
        fc = FakeClock()
        jr = JourneyRecorder(clock=fc, enabled=False)
        reps = [_server() for _ in range(2)]
        router = ReplicaRouter(reps, policy="least_loaded", journeys=jr)
        rid = router.submit(_prompt(4, 5), max_new_tokens=3)
        for _ in range(50):
            router.poll()
            busy = False
            for rep in reps:
                if rep.queue_depth() or rep.in_flight():
                    rep.step()
                    busy = True
            if not busy:
                break
        np.testing.assert_array_equal(router.wait(rid, timeout=5),
                                      stub_tokens([4, 5], 3))
        assert fc.reads == 0 and len(jr) == 0
        assert router.journey(rid) is None


# --------------------------------------------------------------------------
# Per-tick dispatch profile (ROADMAP item-4 baseline)
# --------------------------------------------------------------------------
class TestTickDispatchProfile:
    def test_recorder_tick_events_carry_per_op_profile(self):
        rec = FlightRecorder()
        srv = _server(recorder=rec)
        r0 = srv.submit(_prompt(1, 2, 3), max_new_tokens=5)
        r1 = srv.submit(_prompt(3, 1), max_new_tokens=5)
        out = srv.run()
        np.testing.assert_array_equal(out[r0], stub_tokens([1, 2, 3], 5))
        np.testing.assert_array_equal(out[r1], stub_tokens([3, 1], 5))
        ticks = rec.events(kind="tick")
        assert ticks, "no tick profiles recorded"
        first = ticks[0]["dispatches"]
        # admission tick: ragged prefill launch + slot-state pushes +
        # block-table sync + the decode program itself
        assert first["prefill"] >= 1 and first["decode"] == 1
        assert first["state_push"] >= 1 and first["block_table"] >= 1
        assert ticks[0]["total"] == sum(first.values())
        # steady-state decode ticks: decode only
        assert any(e["dispatches"] == {"decode": 1} for e in ticks)
        assert srv.stats["tick_dispatches"] == \
            sum(e["total"] for e in ticks)

    def test_dispatch_metrics_published(self):
        tele = ServerTelemetry()
        srv = _server(telemetry=tele)
        srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        srv.run()
        h = tele.registry.get("serving_tick_dispatches")
        assert h is not None and h.count >= 1
        c = tele.registry.get("server_dispatches_total")
        assert c.labels(op="decode").value >= 1
        assert c.labels(op="prefill").value >= 1
        assert srv.stats["tick_dispatches"] == h.sum


# --------------------------------------------------------------------------
# Server-side recorder events + postmortems
# --------------------------------------------------------------------------
def _pressure_server(rec=None, tele=None, breaker=None, fi=None):
    """Optimistic server sized so the high-priority grower preempts the
    low-priority victim: usable pool 5 pages, two slots."""
    return _server(max_slots=2, num_pages=6, admission="optimistic",
                   recorder=rec, telemetry=tele, breaker=breaker,
                   fault_injector=fi,
                   retry_policy=RetryPolicy(base_delay_s=0.0, jitter=0.0))


V_PROMPT = [5, 6, 7, 8, 9, 10, 11, 12]    # one FULL page: its preempt
#                                           teardown donates a node


def _park_victim(srv):
    """Submit a high-priority grower + low-priority victim and step
    until the victim is parked (still parked: pool exhausted)."""
    f = srv.submit(_prompt(1, 2, 3, 4), max_new_tokens=28, priority=1)
    v = srv.submit(_prompt(*V_PROMPT), max_new_tokens=28, priority=0)
    _drive(srv, stop=lambda: srv.preempt_pressure() > 0)
    assert srv.preempt_pressure() > 0, "victim never parked"
    return f, v


class TestServerRecorder:
    def test_lifecycle_event_sequence(self):
        rec = FlightRecorder()
        srv = _server(recorder=rec)
        rid = srv.submit(_prompt(9, 9), max_new_tokens=3)
        srv.run()
        kinds = [e["kind"] for e in rec.events()]
        assert kinds[0] == "admit"
        assert "finish" in kinds and "tick" in kinds
        fin = rec.events(kind="finish")[0]
        assert fin["rid"] == rid and fin["tokens"] == 3

    def test_preempt_grow_replay_events(self):
        rec = FlightRecorder()
        srv = _pressure_server(rec=rec)
        f, v = _park_victim(srv)
        _drive(srv)                     # run to completion
        np.testing.assert_array_equal(
            srv._results[v], stub_tokens(V_PROMPT, 28))
        kinds = [e["kind"] for e in rec.events()]
        assert "grow" in kinds and "preempt" in kinds
        assert "replay" in kinds and "donate" in kinds
        pre = rec.events(kind="preempt")[0]
        assert pre["rid"] == v
        rep = rec.events(kind="replay")
        assert rep and rep[0]["rid"] == v

    def test_breaker_open_postmortem_has_parked_queue_and_pool(self):
        """Acceptance: a chaos-killed request produces a postmortem
        bundle containing the parked queue and the pool balance."""
        rec = FlightRecorder()
        srv = _pressure_server(
            rec=rec, breaker=CircuitBreaker(failure_threshold=1))
        f, v = _park_victim(srv)
        srv._on_tick_failure(RuntimeError("chaos"))   # retries exhausted
        bundles = srv.postmortems()
        assert bundles, "breaker open captured no bundle"
        b = bundles[-1]
        assert b["reason"] == "breaker_open"
        assert any(p["rid"] == v for p in b["parked"])
        assert b["pool_balance"]["preempted"] >= 1
        assert b["pool_balance"]["free"] + b["pool_balance"]["live"] \
            + b["pool_balance"]["pinned"] + b["pool_balance"]["cached"] \
            == srv._kv.num_pages - 1
        assert b["block_table"]["slots"]           # occupancy captured
        assert "cached_pages" in b["prefix_cache"]
        assert any(e["kind"] == "breaker" for e in b["events"])
        # both requests were killed typed — the bundle is their record
        assert isinstance(srv.failures[v], CircuitOpenError)
        assert isinstance(srv.failures[f], CircuitOpenError)

    def test_request_failure_captures_bundle_and_fault_metric(self):
        rec = FlightRecorder()
        tele = ServerTelemetry()
        fi = FaultInjector(seed=0).on(faults.PREFILL, schedule=[0])
        srv = _server(recorder=rec, telemetry=tele, fault_injector=fi)
        rid = srv.submit(_prompt(1, 1, 1), max_new_tokens=4)
        srv.run()
        assert rid in srv.failures
        bundles = srv.postmortems()
        assert bundles and bundles[-1]["reason"] == "request_failed"
        assert bundles[-1]["rid"] == rid
        # satellite: the fire is visible on /metrics AND in the ring
        fires = tele.registry.get("fault_fires_total")
        assert fires.labels(point=faults.PREFILL).value == 1
        assert any(e["kind"] == "fault"
                   and e["point"] == faults.PREFILL
                   for e in rec.events())

    def test_shared_injector_counts_fires_in_every_registry(self):
        """A fleet-shared injector must make a storm visible on EVERY
        attached registry, not just the last-constructed component's
        (regression: publish_to was last-wins)."""
        fi = FaultInjector(seed=0).on(faults.PREFILL, schedule=[0])
        tele0, tele1 = ServerTelemetry(), ServerTelemetry()
        srv0 = _server(telemetry=tele0, fault_injector=fi)
        _server(telemetry=tele1, fault_injector=fi)   # later component
        srv0.submit(_prompt(1,), max_new_tokens=2)
        srv0.run()                    # the fire happens on srv0
        for reg in (tele0.registry, tele1.registry):
            assert reg.get("fault_fires_total") \
                .labels(point=faults.PREFILL).value == 1

    def test_evict_oldest_shed_records_fail_but_no_bundle(self):
        """Shedding under overload is EXPECTED: the recorder gets the
        fail event, but no postmortem bundle is captured on the
        submit() hot path (a storm of sheds must not flood the bounded
        bundle store)."""
        rec = FlightRecorder()
        srv = _server(recorder=rec, max_queue=1,
                      shed_policy="evict_oldest")
        old = srv.submit(_prompt(1,), max_new_tokens=2)
        srv.submit(_prompt(2,), max_new_tokens=2)    # sheds `old`
        assert old in srv.failures
        assert any(e["kind"] == "fail" and e["rid"] == old
                   for e in rec.events())
        assert srv.postmortems() == []

    def test_kill_captures_crash_scene(self):
        rec = FlightRecorder()
        srv = _server(recorder=rec)
        rid = srv.submit(_prompt(2, 2), max_new_tokens=4)
        srv.kill()
        b = srv.postmortems()[-1]
        assert b["reason"] == "killed" and rid in b["queue"]
        assert any(e["kind"] == "killed" for e in rec.events())
        assert any(e["kind"] == "health" and e["state"] == "dead"
                   for e in rec.events())


# --------------------------------------------------------------------------
# parked/replay span phases (PR-2 satellite)
# --------------------------------------------------------------------------
class TestPreemptionSpans:
    def test_parked_and_replay_spans_in_timeline(self):
        tele = ServerTelemetry()
        srv = _pressure_server(tele=tele)
        f, v = _park_victim(srv)
        _drive(srv)
        names = {e["name"] for e in tele.tracer.events()
                 if e.get("args", {}).get("rid") == v}
        assert "request.parked" in names
        assert "request.replay" in names
        # the un-preempted grower keeps the normal phase names
        f_names = {e["name"] for e in tele.tracer.events()
                   if e.get("args", {}).get("rid") == f}
        assert "request.parked" not in f_names
        assert "request.replay" not in f_names


# --------------------------------------------------------------------------
# The journey acceptance scenario + fleet Perfetto export
# --------------------------------------------------------------------------
def _fleet_drive(router, reps, max_iters=3000):
    idle = 0
    for _ in range(max_iters):
        router.poll()
        busy = False
        for rep in reps:
            if rep.health == "dead":
                continue
            if rep.queue_depth() or rep.in_flight() \
                    or rep.preempt_pressure():
                rep.step()
                busy = True
        if busy:
            idle = 0
        else:
            idle += 1
            if idle >= 2:
                return
    raise AssertionError("fleet drive did not converge")


class TestJourneyAcceptance:
    def _scenario(self):
        """One request is routed to replica0, stranded by its death
        while queued, failed over to replica1, admitted there,
        preempted by a higher-priority grower, replayed bit-exactly,
        and finished — the full ISSUE-10 acceptance path."""
        jr = JourneyRecorder()
        reps = [_server(max_slots=2, num_pages=6,
                        admission="optimistic",
                        telemetry=ServerTelemetry())
                for _ in range(2)]
        router = ReplicaRouter(reps, policy="least_loaded", journeys=jr,
                               recorder=FlightRecorder())
        v_prompt = [5, 6, 7, 8]
        # victim first: both replicas idle -> replica0 takes it
        v = router.submit(_prompt(*v_prompt), max_new_tokens=28,
                          priority=0)
        # grower second: replica0 now loaded -> replica1 takes it
        f = router.submit(_prompt(1, 2, 3, 4), max_new_tokens=28,
                          priority=1)
        assert router._routes[v].idx == 0
        assert router._routes[f].idx == 1
        reps[1].step()                  # admit the grower on replica1
        reps[0].kill()                  # V still queued on the corpse
        _fleet_drive(router, reps)
        out = router.wait(v, timeout=10)
        np.testing.assert_array_equal(out, stub_tokens(v_prompt, 28))
        np.testing.assert_array_equal(router.wait(f, timeout=10),
                                      stub_tokens([1, 2, 3, 4], 28))
        return router, reps, v, f

    def test_complete_journey_across_replicas(self):
        router, reps, v, f = self._scenario()
        tl = router.journey(v)
        phases = [e["phase"] for e in tl]
        # every acceptance phase present, in causal order
        expected = ["submitted", "dispatched", "queued", "evacuated",
                    "dispatched", "queued", "admitted", "first_token",
                    "preempted", "replay", "finished", "collected"]
        it = iter(phases)
        missing = [p for p in expected if p not in it]
        assert not missing, \
            f"phases {missing} missing/out of order in {phases}"
        # hops carry their true locations
        assert ("queued", "replica0") in \
            [(e["phase"], e["where"]) for e in tl]
        assert ("evacuated", "router") in \
            [(e["phase"], e["where"]) for e in tl]
        wheres = {e["where"] for e in tl}
        assert {"router", "replica0", "replica1"} <= wheres
        # replica death also captured a fleet postmortem with routing
        bundles = router.postmortems()
        dead = [b for b in bundles if b["reason"] == "replica 0 dead"]
        assert dead and dead[0]["source"] == "router"
        assert dead[0]["replicas"][0]["health"] == "dead"
        assert "routes" in dead[0]["routing"]

    def test_fleet_perfetto_export_one_connected_flow(self, tmp_path):
        router, reps, v, f = self._scenario()
        path = tmp_path / "fleet.json"
        n = router.export_fleet_trace(str(path))
        payload = json.loads(path.read_text())
        evs = payload["traceEvents"]
        assert len(evs) == n
        # per-process naming: router + one pid per replica
        names = {e["pid"]: e["args"]["name"] for e in evs
                 if e.get("ph") == "M"}
        assert names == {0: "router", 1: "replica0", 2: "replica1"}
        # each replica's tracer spans landed on its own pid
        assert any(e.get("ph") == "X" and e["pid"] == 2 for e in evs)
        # the failed-over journey is ONE connected flow: its flow
        # events share an id and span router + both replicas
        flows = [e for e in evs
                 if e.get("cat") == "journey" and e.get("id") == f"r{v}"]
        assert len(flows) >= 3
        assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
        assert {e["pid"] for e in flows} == {0, 1, 2}
        # journey phase instants rendered at the emitting hop's pid
        marks = [e for e in evs if e.get("ph") == "i"
                 and e.get("args", {}).get("journey") == f"r{v}"]
        assert any(m["name"] == "journey.preempted" and m["pid"] == 2
                   for m in marks)

    def test_flow_steps_bind_to_journey_events(self, tmp_path):
        """ISSUE 12 satellite (PR 9 known cut): flow steps bind to the
        JOURNEY EVENTS themselves — one step per event at its exact
        (ts, pid) — not to consecutive-``where`` groups. An A->B->A
        bounce whose return hop emits MORE events at A must render an
        arrow anchored at each event, so the bounce reads as two
        distinct crossings (the old grouping collapsed the extra A
        events into the group's first timestamp)."""
        fc = FakeClock()
        jr = JourneyRecorder(clock=fc)
        router = ReplicaRouter([_server()], journeys=jr)
        h = jr.begin("r0", where="router")
        script = [("submitted", "router"), ("dispatched", "router"),
                  ("queued", "replica0"), ("evacuated", "router"),
                  ("held", "router"), ("dispatched", "router")]
        for phase, where in script:
            fc.advance(1.0)
            jr.event("r0", phase, where)
        path = tmp_path / "bounce.json"
        router.export_fleet_trace(str(path))
        evs = json.loads(path.read_text())["traceEvents"]
        flows = [e for e in evs
                 if e.get("cat") == "journey" and e.get("id") == "r0"]
        # one flow step per journey event, phased s/t.../f
        assert len(flows) == len(script)
        assert [e["ph"] for e in flows] == \
            ["s"] + ["t"] * (len(script) - 2) + ["f"]
        # each step anchored at ITS event's pid and timestamp — the
        # bounce back to the router contributes three distinct anchors,
        # not one collapsed hop at the group's first event
        marks = [e for e in evs if e.get("ph") == "i"
                 and e.get("args", {}).get("journey") == "r0"]
        assert [(f["pid"], f["ts"]) for f in flows] == \
            [(m["pid"], m["ts"]) for m in marks]
        assert [f["pid"] for f in flows] == [0, 0, 1, 0, 0, 0]

    def test_single_location_journey_draws_no_flow(self, tmp_path):
        jr = JourneyRecorder()
        router = ReplicaRouter([_server()], journeys=jr)
        jr.begin("r9", where="router")
        jr.event("r9", "submitted", "router")
        jr.event("r9", "collected", "router")
        path = tmp_path / "flat.json"
        router.export_fleet_trace(str(path))
        evs = json.loads(path.read_text())["traceEvents"]
        assert not [e for e in evs if e.get("cat") == "journey"]


# --------------------------------------------------------------------------
# /debug endpoints
# --------------------------------------------------------------------------
def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, json.loads(r.read().decode())


class TestDebugEndpoints:
    def test_router_journey_and_postmortem_endpoints(self):
        jr = JourneyRecorder()
        reps = [_server(telemetry=ServerTelemetry(),
                        recorder=FlightRecorder())
                for _ in range(2)]
        router = ReplicaRouter(reps, policy="least_loaded", journeys=jr,
                               recorder=FlightRecorder(),
                               telemetry=True)
        rid = router.submit(_prompt(3, 3), max_new_tokens=3)
        for _ in range(50):
            router.poll()
            if not any(rep.queue_depth() or rep.in_flight()
                       for rep in reps):
                break
            for rep in reps:
                if rep.queue_depth() or rep.in_flight():
                    rep.step()
        reps[0].kill()
        router.poll()                    # dead-replica postmortem
        ms = serve_metrics(router)
        try:
            status, body = _get(f"{ms.url}/debug/journey/{rid}")
            assert status == 200 and body["rid"] == str(rid)
            assert body["journey"][0]["phase"] == "submitted"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{ms.url}/debug/journey/424242")
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{ms.url}/debug/journey/not-a-rid")
            assert ei.value.code == 404
            status, body = _get(f"{ms.url}/debug/postmortem")
            assert status == 200
            reasons = [b["reason"] for b in body["postmortems"]]
            assert "replica 0 dead" in reasons
        finally:
            ms.close()

    def test_server_postmortem_endpoint_and_no_journey(self):
        srv = _server(telemetry=True, recorder=FlightRecorder())
        rid = srv.submit(_prompt(7,), max_new_tokens=2)
        srv.kill()
        ms = serve_metrics(srv)
        try:
            status, body = _get(f"{ms.url}/debug/postmortem")
            assert status == 200
            assert body["postmortems"][-1]["reason"] == "killed"
            assert rid in body["postmortems"][-1]["queue"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"{ms.url}/debug/journey/0")
            assert ei.value.code == 404    # servers mint no journeys
        finally:
            ms.close()


# --------------------------------------------------------------------------
# Chaos: same-seed storms replay identical recorder sequences
# --------------------------------------------------------------------------
@pytest.mark.chaos
class TestChaosDeterminism:
    def _storm(self, seed):
        rec = FlightRecorder()
        fi = (FaultInjector(seed=seed)
              .on(faults.PREFILL, probability=0.25)
              .on(faults.DECODE_TICK, probability=0.15)
              .on(faults.KV_GROW, probability=0.1)
              .on(faults.SERVER_PREEMPT, probability=0.2))
        srv = _pressure_server(rec=rec, fi=fi)
        rng = np.random.default_rng(7)
        rids = []
        for _ in range(6):
            p = rng.integers(0, 16, (int(rng.integers(3, 9)),))
            rids.append(srv.submit(p.astype(np.int32),
                                   max_new_tokens=12,
                                   priority=int(rng.integers(0, 3))))
        _drive(srv)
        results = {r: srv._results.get(r) for r in rids}
        strip = [{k: v for k, v in e.items() if k != "t"}
                 for e in rec.events()]
        return strip, fi.trace, results, srv

    def test_same_seed_identical_event_sequence(self):
        evs1, trace1, res1, srv1 = self._storm(31)
        evs2, trace2, res2, srv2 = self._storm(31)
        assert trace1 == trace2          # injector contract (sanity)
        assert evs1 == evs2              # recorder sequence contract
        for r in res1:
            if res1[r] is None:
                assert res2[r] is None
            else:
                np.testing.assert_array_equal(res1[r], res2[r])
        # the storm fired and was recorded; no pages leaked
        assert any(e["kind"] == "fault" for e in evs1)
        bal = srv1.pool_balance()
        assert bal[1] == 0
        assert bal[0] + bal[2] + bal[3] == srv1._kv.num_pages - 1

    def test_different_seed_differs(self):
        evs1, trace1, _, _ = self._storm(31)
        evs2, trace2, _, _ = self._storm(32)
        assert trace1 != trace2 or evs1 != evs2


# --------------------------------------------------------------------------
# Lints (wired into tier-1 like check_no_bare_except)
# --------------------------------------------------------------------------
def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMetricDocsLint:
    def test_repo_is_clean(self, capsys):
        mod = _load_script("check_metric_docs")
        assert mod.main(["check_metric_docs.py"]) == 0
        assert "OK" in capsys.readouterr().out

    def test_new_metrics_are_registered_and_seen(self):
        mod = _load_script("check_metric_docs")
        names = mod.registered_metrics(os.path.join(REPO, "paddle_tpu"))
        for required in ("serving_tick_dispatches",
                         "server_dispatches_total",
                         "fault_fires_total",
                         "router_orphaned_total"):
            assert required in names, f"{required} not found by scan"

    def test_detects_drift(self):
        mod = _load_script("check_metric_docs")
        missing = mod.undocumented(
            {"bogus_metric_total": ["x.py"],
             "serving_tick_seconds": ["y.py"]},
            "only serving_tick_seconds is documented here")
        assert missing == [("bogus_metric_total", ["x.py"])]
