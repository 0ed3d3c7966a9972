"""Kill-drill acceptance (ISSUE 12): real process boundaries.

Every prior failover test "killed" a replica with a method call. Here
the replica is a SPAWNED PROCESS behind the wire protocol and the
crash is ``SIGKILL`` — no atexit, no drain, no goodbye frame — under a
20-30% ``net.*`` fault storm. The drill asserts the full robustness
chain end to end:

- the supervisor detects the loss (heartbeats stop with the wire),
- the evacuated queue REPLAYS BIT-EXACT on the sibling process
  (greedy and seeded-sampled chains; seeds were resolved at router
  submit),
- requests caught mid-decode flush their streamed partials,
- survivors leak zero pool pages,
- and the failed-over request's journey renders as ONE connected flow
  across process boundaries in the fleet Perfetto trace.

Spawned processes pay a fresh interpreter + first decode compile each
(~5 s on this 1-cpu CPU box), so this file keeps the fleet small; it
is the slowest of the ``net`` suites but inside the tier-1 budget.
"""
import json
import os
import signal
import socket
import time

import numpy as np
import pytest

from _remote_stub import make_stub_server
from _serving_stub import StubModel
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.remote import RemoteReplica, spawn_replica_host
from paddle_tpu.inference.router import ReplicaRouter
from paddle_tpu.inference.transport import NetDrop
from paddle_tpu.reliability import (NET_CONNECT, NET_RECV, NET_SEND,
                                    FaultInjector, QueueFullError,
                                    ReplicaLostError)


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

SERVER_KW = {"max_slots": 2, "max_cache_len": 64, "page_size": 8}


@pytest.fixture
def procs():
    spawned = []
    yield spawned
    for proc in spawned:
        if proc.is_alive():
            proc.kill()
        proc.join(10)


@pytest.mark.parametrize(
    "do_sample",
    [False,
     # the greedy drill stays tier-1; sampled doubles the spawn+compile
     # cost to cover seed replay, which test_preemption already pins
     # in-process
     pytest.param(True, marks=pytest.mark.slow)],
    ids=["greedy", "sampled"])
def test_sigkill_drill_under_net_storm(procs, tmp_path, do_sample):
    server_kw = dict(SERVER_KW, do_sample=do_sample, telemetry=True)
    if do_sample:
        server_kw["temperature"] = 1.3
    addrs = []
    for _ in range(2):
        proc, addr = spawn_replica_host(
            make_stub_server, server_kw, heartbeat_s=0.05,
            start_server=True)
        procs.append(proc)
        addrs.append(addr)
    fi = FaultInjector(seed=42, enabled=False) \
        .on(NET_SEND, probability=0.25, error=NetDrop) \
        .on(NET_RECV, probability=0.20, error=NetDrop) \
        .on(NET_CONNECT, probability=0.25)
    reps = [RemoteReplica(addr, fault_injector=fi, call_timeout_s=1.0,
                          dead_after_s=0.6, draining_after_s=0.3)
            for addr in addrs]
    router = ReplicaRouter(reps, policy="least_loaded", journeys=True,
                           recorder=True)
    router.start(poll_interval=0.05, start_replicas=False)
    def submit_retrying(p, n, deadline):
        # a real client retries transient fleet-wide refusals: the
        # storm drops dispatch frames, and on this 1-cpu box a child's
        # first decode COMPILE can starve its heartbeat thread long
        # enough to look momentarily dead
        while True:
            try:
                return router.submit(p, max_new_tokens=n)
            except (ReplicaLostError, QueueFullError):
                assert time.monotonic() < deadline, \
                    "fleet never accepted a submit"
                time.sleep(0.05)

    try:
        # warm both children's decode compiles OUTSIDE the storm so
        # the kill lands mid-decode, not mid-compile
        deadline = time.monotonic() + 120
        warm = [submit_retrying(np.asarray([9, i + 1], np.int32), 2,
                                deadline) for i in range(4)]
        for rid in warm:
            router.wait(rid, timeout=120)

        K, budget = 8, 20
        prompts = [np.asarray([5, 3, i + 1], np.int32) for i in range(K)]
        fi.arm()                         # the 20-30% net.* storm is ON
        deadline = time.monotonic() + 90
        rids = [submit_retrying(p, budget, deadline) for p in prompts]
        seeds = {}
        with router._lock:
            for rid in rids:
                seeds[rid] = router._routes[rid].item.seed

        # SIGKILL a replica that is BOTH mid-decode (>= 1 request
        # already streaming -> a partial to flush) and holding queued
        # work (>= 1 request with no tokens -> a bit-exact requeue):
        # the drill then must exercise both failover paths
        deadline = time.monotonic() + 60
        victim = None
        while victim is None:
            for idx, rep in enumerate(reps):
                queued, decoding = rep._mirror_counts()
                if queued >= 1 and decoding >= 1:
                    victim = idx
                    break
            if victim is None:
                assert time.monotonic() < deadline, \
                    "fleet never reached mid-decode-with-backlog " \
                    "under the storm"
                time.sleep(0.005)
        with router._lock:               # ROUTER rids routed to the
            victim_rids = {rid for rid, rt in     # victim at kill time
                           router._routes.items() if rt.idx == victim}
        os.kill(procs[victim].pid, signal.SIGKILL)
        procs[victim].join(10)

        # supervisor detects (wire death = heartbeats stop), evacuates,
        # requeues onto the surviving PROCESS; then calm the storm so
        # the drain converges promptly
        deadline = time.monotonic() + 60
        while router.stats["evacuations"] < 1 \
                or router.stats["requeued"] < 1:
            assert time.monotonic() < deadline, \
                f"no failover observed: {router.stats}"
            time.sleep(0.02)
        fi.disarm()

        results = {rid: router.wait(rid, timeout=120) for rid in rids}

        # bit-exact parity against a local oracle server running the
        # SAME resolved seeds: full results must match exactly, a
        # flushed partial must be an exact prefix
        oracle_kw = {k: v for k, v in server_kw.items()
                     if k != "telemetry"}
        oracle = ContinuousBatchingServer(StubModel(), **oracle_kw)
        orid = {rid: oracle.submit(p, max_new_tokens=budget,
                                   seed=seeds[rid])
                for rid, p in zip(rids, prompts)}
        expected = oracle.run()
        full = partial = 0
        for rid in rids:
            exp, got = expected[orid[rid]], results[rid]
            if len(got) == len(exp):
                np.testing.assert_array_equal(got, exp)
                full += 1
            else:
                assert len(got) < len(exp)
                np.testing.assert_array_equal(got, exp[:len(got)])
                partial += 1
                assert rid in victim_rids   # only the crash flushes
        assert full + partial == K
        assert full >= 1                    # something replayed whole
        assert partial >= 1                 # the mid-decode flush ran

        # zero page leaks on the survivor, over the wire
        survivor = reps[1 - victim]
        bal = survivor.pool_balance()
        assert bal is not None and bal[1] == 0, f"leaked: {bal}"

        # the failed-over journey is ONE connected flow across
        # process boundaries in the merged fleet trace. Prefer a
        # requeued rid whose survivor-side journey pushes survived the
        # storm (they are push frames — the drop chaos can eat them),
        # else any fully replayed victim rid: router + dead-replica
        # pids already prove the boundary crossing.
        replayed = [rid for rid in rids if rid in victim_rids
                    and len(results[rid]) == budget]
        assert replayed
        survivor_where = f"replica{1 - victim}"
        requeued_rid = next(
            (rid for rid in replayed
             if any(e["where"] == survivor_where
                    for e in router._jrec.journey(f"r{rid}") or ())),
            replayed[0])
        path = tmp_path / "fleet.json"
        router.export_fleet_trace(str(path))
        evs = json.loads(path.read_text())["traceEvents"]
        flows = [e for e in evs if e.get("cat") == "journey"
                 and e.get("id") == f"r{requeued_rid}"]
        assert len(flows) >= 2
        assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
        pids = {e["pid"] for e in flows}
        assert len(pids) >= 2               # crossed a process boundary
    finally:
        router.stop(drain=False, timeout=20, stop_replicas=False)
        for rep in reps:
            rep.close()
