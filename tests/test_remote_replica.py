"""Process-isolated replica protocol (ISSUE 12), fast half: every test
here runs the REAL wire protocol over loopback TCP with the
``ReplicaHost`` living on threads in this process — full transport
coverage without process-spawn cost. The spawned-process drills
(SIGKILL, partition storms at scale) live in test_process_fleet.py.

Covers: submit/wait/stream parity over the wire, typed error transit,
deadline re-anchoring, pushed-digest routing reads + the staleness
walk (fresh -> draining -> dead), wire and synthesized evacuation,
router-over-remote routing/failover/rolling-restart, /fleet over
remote snapshots, and the frame-corruption fuzz contract against a
live host."""
import json
import random
import socket
import struct
import threading
import time

import numpy as np
import pytest

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.remote import ReplicaHost, RemoteReplica
from paddle_tpu.inference.router import ReplicaRouter
from paddle_tpu.reliability import (DeadlineExceeded, FaultInjector,
                                    QueueFullError, RequestCancelled,
                                    TransportError)


def _loopback_available():
    try:
        s = socket.create_server(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


pytestmark = [
    pytest.mark.net,
    pytest.mark.skipif(not _loopback_available(),
                       reason="cannot bind a loopback socket here"),
]


def _server(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 8)
    return ContinuousBatchingServer(StubModel(), **kw)


@pytest.fixture
def fleet():
    """Tracked hosts/replicas/routers torn down after each test."""
    made = {"hosts": [], "reps": [], "routers": [], "servers": []}

    def host_rep(heartbeat_s=0.02, server_kw=None, rep_kw=None):
        srv = _server(**(server_kw or {}))
        host = ReplicaHost(srv, heartbeat_s=heartbeat_s).start()
        rep = RemoteReplica(host.address, **(rep_kw or {}))
        made["hosts"].append(host)
        made["reps"].append(rep)
        made["servers"].append(srv)
        return host, rep, srv

    made["host_rep"] = host_rep
    yield made
    for router in made["routers"]:
        try:
            router.stop(drain=False, timeout=10, stop_replicas=False)
        except RuntimeError:
            pass
    for rep in made["reps"]:
        rep.close()
    for host in made["hosts"]:
        host.close()
    for srv in made["servers"]:
        if srv._thread is not None:
            try:
                srv.stop(timeout=10)
            except RuntimeError:
                pass


def _prompt(*toks):
    return np.asarray(toks, np.int32)


class TestWireContract:
    def test_submit_wait_bit_exact_with_streaming(self, fleet):
        _, rep, _ = fleet["host_rep"]()
        rep.start()
        chunks = []
        p = _prompt(2, 5, 9)
        rid = rep.submit(p, max_new_tokens=6,
                         on_token=lambda r, t: chunks.append(list(t)))
        out = rep.wait(rid, timeout=20)
        exp = stub_tokens(p, 6)
        np.testing.assert_array_equal(out, exp)
        # the stream delivered every token exactly once, in order
        streamed = [t for c in chunks for t in c]
        assert streamed == list(exp)

    def test_sampled_chain_parity_with_local_server(self, fleet):
        """Seeds resolve client-side semantics identically: the same
        (prompt, seed) on a remote and a local server draw the same
        sampled chain — the requeue-parity foundation."""
        _, rep, _ = fleet["host_rep"](
            server_kw={"do_sample": True, "temperature": 1.3})
        rep.start()
        local = _server(do_sample=True, temperature=1.3)
        p = _prompt(4, 4, 8)
        rid = rep.submit(p, max_new_tokens=8, seed=123)
        got = rep.wait(rid, timeout=20)
        lrid = local.submit(p, max_new_tokens=8, seed=123)
        np.testing.assert_array_equal(got, local.run()[lrid])

    def test_default_seed_reported_to_mirror_matches_server(self, fleet):
        """submit(seed=None): the host must report the SERVER's actual
        resolved default to the client mirror (pins the default-seed
        rule the host mirrors from ContinuousBatchingServer.submit) —
        a drifted copy would silently break synthesized-requeue
        parity."""
        _, rep, srv = fleet["host_rep"](
            server_kw={"do_sample": True, "seed": 31})
        rid = rep.submit(_prompt(2, 2), max_new_tokens=4)   # no seed
        with rep._state_lock:
            mirrored = rep._mirror[rid].seed
        with srv._lock:
            actual = next(i.seed for i in srv._queue if i.rid == rid)
        assert mirrored == actual == 31 + rid

    def test_typed_errors_cross_the_wire(self, fleet):
        _, rep, _ = fleet["host_rep"](
            server_kw={"max_queue": 0, "shed_policy": "reject"})
        with pytest.raises(DeadlineExceeded):
            rep.submit(_prompt(1), max_new_tokens=2, deadline_s=-1)
        with pytest.raises(QueueFullError):
            rep.submit(_prompt(1), max_new_tokens=2)

    def test_cancel_queued_raises_typed(self, fleet):
        _, rep, _ = fleet["host_rep"]()     # serve thread NOT started
        rid = rep.submit(_prompt(3, 1), max_new_tokens=4)
        assert rep.cancel(rid) is True
        with pytest.raises(RequestCancelled):
            rep.wait(rid, timeout=5)

    def test_deadline_reanchors_on_host_clock(self, fleet):
        _, rep, _ = fleet["host_rep"]()     # not started: stays queued
        rid = rep.submit(_prompt(7, 7), max_new_tokens=4,
                         deadline_s=0.1)
        time.sleep(0.2)
        rep.start()
        with pytest.raises(DeadlineExceeded):
            rep.wait(rid, timeout=10)

    def test_wire_evacuate_returns_remaining_deadline(self, fleet):
        _, rep, _ = fleet["host_rep"]()     # not started: stays queued
        def sink(rid_, toks):
            pass

        rid = rep.submit(_prompt(6, 2), max_new_tokens=4,
                         on_token=sink, deadline_s=30.0,
                         priority=2)
        harvested = rep.evacuate()
        assert [h.rid for h in harvested] == [rid]
        h = harvested[0]
        np.testing.assert_array_equal(h.ids, _prompt(6, 2))
        assert h.budget == 4 and h.priority == 2
        assert h.on_token is sink           # reattached from the mirror
        # the absolute deadline was rebuilt from remaining seconds
        assert 25.0 < h.deadline - rep._clock.now() <= 30.0
        # the host's queue is actually empty now
        assert rep._call("stats")["admissions"] == 0

    def test_wait_survives_lost_reply_via_delivery_stash(self, fleet):
        """A wait whose REPLY frame is dropped retries and still gets
        the result: the host stashes deliveries idempotently."""
        from paddle_tpu.inference.transport import NetDrop
        from paddle_tpu.reliability import NET_RECV
        _, rep, _ = fleet["host_rep"]()
        rep.start()
        p = _prompt(5, 5)
        rid = rep.submit(p, max_new_tokens=4)
        out = rep.wait(rid, timeout=20)     # settle server-side first
        np.testing.assert_array_equal(out, stub_tokens(p, 4))
        # now make the client drop the next reply frame: the SECOND
        # wait for the same rid must still return the stashed result
        fi = FaultInjector(seed=2).on(NET_RECV, schedule=[0],
                                      error=NetDrop)
        rep._conn._faults = fi
        out2 = rep._call("wait", rid=rid, timeout=0.5,
                         reply_timeout=5.0)
        assert list(out2) == list(stub_tokens(p, 4))


class TestDigestsAndStaleness:
    def test_routing_reads_come_from_pushed_digest(self, fleet):
        host, rep, srv = fleet["host_rep"]()
        for i in range(3):
            rep.submit(_prompt(1, 1, i + 1), max_new_tokens=2)
        deadline = time.monotonic() + 5
        while rep.queue_depth() != 3:
            assert time.monotonic() < deadline, "digest never refreshed"
            time.sleep(0.01)
        assert rep.queue_depth() == srv.queue_depth() == 3
        assert rep.health == "healthy"
        assert rep.stats["admissions"] == 0

    def test_staleness_walks_draining_then_dead_then_recovers(self, fleet):
        host, rep, _ = fleet["host_rep"](
            rep_kw={"draining_after_s": 0.15, "dead_after_s": 0.4})
        assert rep.health == "healthy"
        host.pause_heartbeats()
        time.sleep(0.25)
        assert rep.health == "draining"     # missed a few heartbeats
        time.sleep(0.3)
        assert rep.health == "dead"         # missed many
        host.resume_heartbeats()
        deadline = time.monotonic() + 5
        while rep.health != "healthy":
            assert time.monotonic() < deadline, "never recovered"
            time.sleep(0.01)

    def test_sketch_crosses_the_wire_for_affinity(self, fleet):
        from paddle_tpu.inference.prefix_cache import prefix_fingerprints
        _, rep, srv = fleet["host_rep"]()
        rep.start()
        p = np.arange(16, dtype=np.int32)   # two full pages to donate
        rid = rep.submit(np.concatenate([p, _prompt(1)]),
                         max_new_tokens=2)
        rep.wait(rid, timeout=20)
        deadline = time.monotonic() + 5
        fps = prefix_fingerprints(p, 8)
        while not all(fp in rep.prefix_sketch() for fp in fps):
            assert time.monotonic() < deadline, "sketch never arrived"
            time.sleep(0.01)


class TestRouterOverRemote:
    def test_affinity_routes_to_the_remote_holding_the_pages(self, fleet):
        reps = [fleet["host_rep"]()[1] for _ in range(3)]
        router = ReplicaRouter(reps)
        fleet["routers"].append(router)
        router.start(poll_interval=0.02)
        shared = np.arange(16, dtype=np.int32) % 16
        for i in range(5):
            p = np.concatenate([shared, _prompt(i + 1)])
            rid = router.submit(p, max_new_tokens=3)
            np.testing.assert_array_equal(router.wait(rid, timeout=30),
                                          stub_tokens(p, 3))
            # let the winner's donation reach the sketch before the
            # next submit routes (digest cadence 0.02s)
            time.sleep(0.08)
        assert router.stats["affinity_hits"] == 4
        assert router.stats["fallbacks"] == 1
        assert max(router.stats["routed"]) == 5

    def test_sigkill_less_crash_failover_bit_exact(self, fleet):
        """host.sever() is the in-process stand-in for a crash: the
        network face disappears, the supervisor detects it, and the
        synthesized evacuation requeues unstreamed requests bit-exact
        on the sibling while streamed ones flush partials."""
        host0, rep0, srv0 = fleet["host_rep"](
            rep_kw={"dead_after_s": 0.3})
        host1, rep1, srv1 = fleet["host_rep"]()
        router = ReplicaRouter([rep0, rep1], policy="least_loaded",
                               telemetry=True)
        fleet["routers"].append(router)
        router.start(poll_interval=0.02)
        # budgets long enough that host0 still holds work when it is
        # severed: four requests of 4 tokens could all finish inside
        # the 20 ms (a loaded box oversleeps), and then nothing is left
        # to evacuate
        new = 48
        rids = [(router.submit(_prompt(2, i + 1), max_new_tokens=new), i)
                for i in range(8)]
        time.sleep(0.02)
        host0.sever()
        outs = {}
        for rid, i in rids:
            outs[rid] = (router.wait(rid, timeout=30), _prompt(2, i + 1))
        full = partial = 0
        for rid, (got, p) in outs.items():
            exp = stub_tokens(p, new)
            if np.array_equal(got, exp):
                full += 1
            else:
                np.testing.assert_array_equal(got, exp[:len(got)])
                partial += 1
        assert full + partial == 8
        assert router.stats["evacuations"] >= 1
        # the survivor leaked nothing
        free, live, pinned, cached = srv1.pool_balance()
        assert live == 0

    def test_mixed_local_and_remote_fleet_failover(self, fleet):
        """The tentpole contract: the router works UNCHANGED over a
        MIX of in-process server objects and remote processes — and a
        remote crash fails over onto the local sibling bit-exact."""
        _, remote, _ = fleet["host_rep"](rep_kw={"dead_after_s": 0.3})
        local = _server()
        fleet["servers"].append(local)
        router = ReplicaRouter([remote, local], policy="least_loaded")
        fleet["routers"].append(router)
        router.start(poll_interval=0.02)
        rids = [(router.submit(_prompt(4, i + 1), max_new_tokens=3), i)
                for i in range(6)]
        for rid, i in rids:
            np.testing.assert_array_equal(
                router.wait(rid, timeout=30),
                stub_tokens(_prompt(4, i + 1), 3))
        routed = router.stats["routed"]
        assert routed[0] > 0 and routed[1] > 0   # both kinds served
        # now crash the remote's network face with work queued on it
        fleet["hosts"][0].sever()
        more = [(router.submit(_prompt(6, i + 1), max_new_tokens=3), i)
                for i in range(4)]
        for rid, i in more:
            got = router.wait(rid, timeout=30)
            exp = stub_tokens(_prompt(6, i + 1), 3)
            np.testing.assert_array_equal(got, exp[:len(got)])
        assert router.health == "degraded"       # local still serving

    def test_rolling_restart_over_the_wire_zero_failures(self, fleet):
        reps = [fleet["host_rep"]()[1] for _ in range(2)]
        router = ReplicaRouter(reps, policy="least_loaded")
        fleet["routers"].append(router)
        router.start(poll_interval=0.02)
        rids = [(router.submit(_prompt(3, i + 1), max_new_tokens=4), i)
                for i in range(6)]
        router.rolling_restart(drain_timeout=60.0)
        for rid, i in rids:
            np.testing.assert_array_equal(
                router.wait(rid, timeout=30),
                stub_tokens(_prompt(3, i + 1), 4))
        assert router.stats["restarts"] == 2

    def test_fleet_page_merges_remote_snapshots(self, fleet):
        from paddle_tpu.telemetry import RouterTelemetry
        rt = RouterTelemetry()
        host, rep, srv = fleet["host_rep"](
            server_kw={"telemetry": True},
            rep_kw={"registry": rt.registry})
        router = ReplicaRouter([rep], telemetry=rt)
        fleet["routers"].append(router)
        router.start(poll_interval=0.02)
        rid = router.submit(_prompt(9, 1), max_new_tokens=3)
        router.wait(rid, timeout=30)
        page = router.fleet_metrics()
        # the remote server's registry crossed the wire into /fleet
        assert "serving_requests_total" in page
        # and the wire itself is accounted for on the client registry
        assert "net_frames_total" in page
        assert "net_call_seconds" in page
        assert "net_heartbeats_total" in page
        snap = router.fleet_snapshot()
        assert snap["serving_requests_total"]["samples"][
            ("finished",)] >= 1


class TestHostFuzz:
    """Satellite: a fuzzer hammering the host's port must never wedge
    a real client's call or kill the host loop."""

    def test_garbage_frames_do_not_kill_host_or_real_client(self, fleet):
        host, rep, _ = fleet["host_rep"]()
        rep.start()
        rng = random.Random(77)     # seeded-PRNG chaos pattern
        raw = socket.create_connection(host.address, timeout=5)
        try:
            for _ in range(30):
                kind = rng.randrange(3)
                if kind == 0:       # garbage payload, valid length
                    junk = bytes(rng.randrange(256)
                                 for _ in range(rng.randrange(1, 60)))
                    raw.sendall(struct.pack("!I", len(junk)) + junk)
                elif kind == 1:     # valid JSON, nonsense op
                    body = json.dumps({"id": rng.randrange(99),
                                       "op": "nope"}).encode()
                    raw.sendall(struct.pack("!I", len(body)) + body)
                else:               # valid JSON, not even a dict
                    body = json.dumps([1, 2, 3]).encode()
                    raw.sendall(struct.pack("!I", len(body)) + body)
            # a real client call still works mid-fuzz
            p = _prompt(8, 3)
            rid = rep.submit(p, max_new_tokens=4)
            np.testing.assert_array_equal(rep.wait(rid, timeout=20),
                                          stub_tokens(p, 4))
            # oversized length prefix severs ONLY the fuzzer's conn
            raw.sendall(struct.pack("!I", 0xFFFFFFFF) + b"xx")
            time.sleep(0.1)
            rid = rep.submit(p, max_new_tokens=2)
            np.testing.assert_array_equal(rep.wait(rid, timeout=20),
                                          stub_tokens(p, 2))
        finally:
            raw.close()

    def test_unknown_op_fails_that_call_typed(self, fleet):
        _, rep, _ = fleet["host_rep"]()
        with pytest.raises(ValueError, match="unknown wire op"):
            rep._call("definitely_not_an_op")
        assert rep.health == "healthy"      # connection survived
        assert rep._call("ping") == "pong"
