"""Live KV-page migration (ISSUE 18): drains hand off mid-decode
state instead of flushing partials.

Contracts pinned here:

- a mid-decode request migrated source -> target continues BIT-EXACT
  (greedy AND seeded-sampled) against a never-migrated oracle, with
  ZERO re-prefill on the target (``prefill_tokens`` and ``admissions``
  stay 0);
- every failure degrades to requeue-replay, typed and leak-free:
  checksum mismatch, injected ``migrate.gather``/``migrate.restore``
  chaos, a target with no free slot, a SIGKILLed target process — the
  source resumes the paused slot bit-exactly and counts
  ``server_migrations_total{result="fallback"}``;
- the wire protocol ships one sha256-checked binary frame per page and
  the client's ``fetch_tokens`` backfill heals token-push gaps a
  ``net.send`` drop storm tears into the stream (the ``_on_tokens``
  regression);
- a 25% chaos storm over ``net.*`` + ``migrate.*`` replays identically
  under the same seed (single-threaded, step()-driven, so the fault
  trace is exact);
- per-shard gathers/scatters are topology-neutral: pages migrate
  between mp=1 and mp=2 pools bit-exactly (real llama sampling, so
  the restored PRNG chain is genuinely exercised).
"""
import random
import socket
import threading
import time

import numpy as np
import pytest

import jax

from _remote_stub import make_stub_server
from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_cache import OutOfPages
from paddle_tpu.inference.remote import ReplicaHost, RemoteReplica
from paddle_tpu.inference.transport import Connection, NetDelay, NetDrop
from paddle_tpu.reliability import (MIGRATE_GATHER, MIGRATE_RESTORE,
                                    NET_PAGE_SEND, NET_RECV, NET_SEND,
                                    FaultInjector, InjectedFault,
                                    MigrationError)

SERVER_KW = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                 page_size=8, num_pages=17)
PROMPT = (np.arange(1, 12, dtype=np.int32) % 13)
BUDGET = 48          # prompt 11 + 48 <= max_cache_len 64; big enough
#                      that the handoff reliably lands mid-decode


def _loopback_available():
    try:
        s = socket.create_server(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


def _sink(got, dt=0.003):
    """A throttling stream callback: 3 ms per chunk keeps the decode
    loop slow enough that migrate_out always catches the request
    mid-decode (callbacks fire on the serving thread)."""
    def cb(rid, toks):
        got.extend(int(t) for t in toks)
        time.sleep(dt)
    return cb


def _wait(pred, timeout=30, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timed out on: {msg}"
        time.sleep(0.005)


def _servers(n, **overrides):
    kw = dict(SERVER_KW)
    kw.update(overrides)
    return [ContinuousBatchingServer(StubModel(), **kw)
            for _ in range(n)]


class _Throttle(NetDelay):
    """Every host send dawdles 10 ms: the deferred token-push callbacks
    fire on the serving thread, so this paces the decode loop and the
    wire drills reliably catch the request MID-decode (the StubModel
    otherwise finishes a 48-token budget in the round-trip window)."""
    SECONDS = 0.01


def _throttle_fi():
    return FaultInjector(seed=1).on(NET_SEND, probability=1.0,
                                    error=_Throttle)


class _StormFactory:
    """probability-1.0 ``net.send`` rule for the drop-storm drill:
    every send fires — most resolve to the pacing delay (keeping the
    stream stretched mid-air), a seeded fraction DROP the frame
    outright, capped so the tail of the stream gets through clean and
    the backfill's repair pushes eventually land."""

    def __init__(self, seed, p_drop=0.25, max_drops=6):
        self.rng = random.Random(seed)
        self.p_drop, self.max_drops = p_drop, max_drops
        self.drops = 0

    def __call__(self):
        if self.drops < self.max_drops \
                and self.rng.random() < self.p_drop:
            self.drops += 1
            return NetDrop("storm drop")
        return _Throttle("pacing")


# =================================================== in-process parity
class TestMigrationInProcess:
    # the greedy half is the tier-1 canary; sampled PRNG re-derivation
    # stays covered tier-1 by the abort test below and in full by the
    # slow wire-sampled parity case
    @pytest.mark.parametrize(
        "do_sample", [False, pytest.param(True, marks=pytest.mark.slow)],
        ids=["greedy", "sampled"])
    def test_mid_decode_migration_bitexact_zero_reprefill(self,
                                                          do_sample):
        """The acceptance drill: pause mid-decode, gather, restore on
        a sibling, resume mid-chain — tokens bit-exact vs a
        never-migrated oracle, zero prefill work on the target, zero
        leaked pages on either end, journey + metrics attributed."""
        kw = dict(do_sample=do_sample)
        if do_sample:
            kw.update(temperature=0.7, top_k=8)
        tgt, oracle = _servers(2, **kw)
        src = ContinuousBatchingServer(
            StubModel(), telemetry=True, journeys=True, recorder=True,
            **dict(SERVER_KW, **kw))
        got = []
        src.start(); tgt.start(); oracle.start()
        try:
            rid_o = oracle.submit(PROMPT, max_new_tokens=BUDGET, seed=5)
            rid = src.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            state, payloads = src.migrate_out(rid)
            assert state["seed"] == 5          # resolved seed travels
            assert state["sha256"] and len(state["sha256"]) \
                == len(payloads)
            new_rid = tgt.migrate_in(state, payloads,
                                     on_token=_sink(got))
            src.migrate_finish(rid)
            out = tgt.wait(new_rid, timeout=60)
            ref = oracle.wait(rid_o, timeout=60)
            np.testing.assert_array_equal(out, ref)
            if not do_sample:
                np.testing.assert_array_equal(
                    out, stub_tokens(PROMPT, BUDGET))
            # the stream healed across the handoff: every token once,
            # in order, no re-delivery of the pre-migration prefix
            _wait(lambda: len(got) >= BUDGET, timeout=10,
                  msg="stream drained")
            assert got == [int(t) for t in ref]
            # zero re-prefill on the target: no admission, no prompt
            # tokens pushed — the restore scatter is priced as
            # page_migrate bytes, not prefill
            assert tgt.stats["prefill_tokens"] == 0
            assert tgt.stats["admissions"] == 0
            assert src.stats["migrations"] == 1
            assert tgt.stats["migrated_in"] == 1
            for s in (src, tgt):
                assert s.pool_balance()[1] == 0
            # attribution: the journey crossed a "migrating" phase and
            # the source counted {result="ok"} with a latency sample
            timeline = src.journey(rid)
            assert any(e["phase"] == "migrating" for e in timeline)
            snap = src._tele.registry.snapshot()
            assert snap["server_migrations_total"]["samples"][
                ("ok",)] == 1
            assert snap["serving_migration_seconds"]["samples"][()][
                "count"] == 1
        finally:
            src.stop(); tgt.stop(); oracle.stop()

    def test_abort_resumes_bitexact_and_counts_fallback(self):
        """migrate_abort re-primes the paused slot (pending token,
        write cursor, PRNG key mid-chain) so the SOURCE finishes the
        stream bit-exactly — the universal fallback every failure
        path below degrades to."""
        src, oracle = _servers(2, do_sample=True, temperature=0.7,
                               top_k=8)
        got = []
        src.start(); oracle.start()
        try:
            rid_o = oracle.submit(PROMPT, max_new_tokens=BUDGET, seed=9)
            rid = src.submit(PROMPT, max_new_tokens=BUDGET, seed=9,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            state, payloads = src.migrate_out(rid)
            assert src.migrate_abort(rid) is True
            assert src.migrate_abort(rid) is False   # idempotent
            np.testing.assert_array_equal(src.wait(rid, timeout=60),
                                          oracle.wait(rid_o, timeout=60))
            assert src.stats["migration_fallbacks"] == 1
            assert src.stats["migrations"] == 0
            assert src.pool_balance()[1] == 0
        finally:
            src.stop(); oracle.stop()

    def test_refusals_typed_and_leak_free(self):
        """Non-migratable requests refuse with ``MigrationError`` (a
        named, wire-marshallable class) without touching the slot:
        unknown rids, finished rids, double migrations, dense pools,
        and tampered payloads/geometry at the restore end."""
        src, tgt = _servers(2)
        (dense,) = _servers(1, cache_backend="dense")
        got = []
        src.start(); tgt.start(); dense.start()
        try:
            with pytest.raises(MigrationError):
                src.migrate_out(12345)                 # unknown rid
            with pytest.raises(MigrationError):
                dense.migrate_out(0)                   # no page pool
            done = src.submit(PROMPT, max_new_tokens=4)
            src.wait(done, timeout=60)
            with pytest.raises(MigrationError):
                src.migrate_out(done)                  # finished rid
            rid = src.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            state, payloads = src.migrate_out(rid)
            with pytest.raises(MigrationError):
                src.migrate_out(rid)                   # already paused
            # target-side refusals, each before any page sticks:
            bad = dict(state, page_size=4)
            with pytest.raises(MigrationError):
                tgt.migrate_in(bad, payloads)          # geometry
            with pytest.raises(MigrationError):
                tgt.migrate_in(state, payloads[:-1])   # page count
            tampered = [[np.array(a) for a in p] for p in payloads]
            tampered[0][0].flat[0] += 1.0
            with pytest.raises(MigrationError):
                tgt.migrate_in(state, tampered)        # e2e sha256
            assert tgt.pool_balance()[1] == 0          # nothing stuck
            assert tgt.stats["migrated_in"] == 0
            # the source still resumes cleanly after all that
            assert src.migrate_abort(rid) is True
            np.testing.assert_array_equal(
                src.wait(rid, timeout=60),
                stub_tokens(PROMPT, BUDGET))
            assert src.pool_balance()[1] == 0
        finally:
            src.stop(); tgt.stop(); dense.stop()

    def test_chaos_gather_and_restore_fall_back(self):
        """``migrate.gather`` fires BEFORE the pause (the faulted
        attempt leaves the slot decoding untouched); ``migrate.restore``
        fires before any allocation on the target — both degrade to
        abort/resume with zero leaked pages anywhere."""
        fi_src = FaultInjector(seed=6).on(MIGRATE_GATHER, schedule=[0])
        fi_tgt = FaultInjector(seed=6).on(MIGRATE_RESTORE, schedule=[0])
        kw = dict(SERVER_KW)
        src = ContinuousBatchingServer(StubModel(),
                                       fault_injector=fi_src, **kw)
        tgt = ContinuousBatchingServer(StubModel(),
                                       fault_injector=fi_tgt, **kw)
        got = []
        src.start(); tgt.start()
        try:
            rid = src.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            with pytest.raises(InjectedFault):
                src.migrate_out(rid)                  # gather chaos
            state, payloads = src.migrate_out(rid)    # fault spent
            with pytest.raises(InjectedFault):
                tgt.migrate_in(state, payloads)       # restore chaos
            assert tgt.pool_balance()[1] == 0
            assert src.migrate_abort(rid) is True
            np.testing.assert_array_equal(
                src.wait(rid, timeout=60),
                stub_tokens(PROMPT, BUDGET))
            assert src.stats["migration_fallbacks"] == 1
            assert src.pool_balance()[1] == 0
        finally:
            src.stop(); tgt.stop()

    def test_target_without_free_slot_refuses_typed(self):
        """A packed target raises ``OutOfPages`` from the normal admit
        path — the router treats it like any restore failure and falls
        back; the source resumes bit-exactly."""
        src, tgt = _servers(2)
        got = []
        src.start(); tgt.start()
        try:
            hold = [tgt.submit(PROMPT, max_new_tokens=BUDGET,
                               on_token=_sink([], dt=0.005))
                    for _ in range(2)]         # both target slots busy
            rid = src.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            state, payloads = src.migrate_out(rid)
            with pytest.raises(OutOfPages):
                tgt.migrate_in(state, payloads)
            assert src.migrate_abort(rid) is True
            np.testing.assert_array_equal(
                src.wait(rid, timeout=60),
                stub_tokens(PROMPT, BUDGET))
            for h in hold:
                tgt.wait(h, timeout=60)
            assert src.pool_balance()[1] == 0
            assert tgt.pool_balance()[1] == 0
        finally:
            src.stop(); tgt.stop()


# ============================================== wire + router + drills
@pytest.mark.net
@pytest.mark.skipif(not _loopback_available(),
                    reason="cannot bind a loopback socket here")
class TestWireMigration:
    @pytest.fixture
    def fleet(self):
        opened = []

        def pair(src_faults=None, **kw):
            src = make_stub_server(num_pages=17, **kw)
            tgt = make_stub_server(num_pages=17, **kw)
            hs = ReplicaHost(src, heartbeat_s=30,
                             fault_injector=src_faults).start()
            ht = ReplicaHost(tgt, heartbeat_s=30).start()
            rs = RemoteReplica(hs.address)
            rt = RemoteReplica(ht.address)
            src.start(); tgt.start()
            opened.extend([(rs, rt), (hs, ht), (src, tgt)])
            return src, tgt, hs, ht, rs, rt

        yield pair
        for rs, rt in opened[0::3]:
            rs.close(); rt.close()
        for hs, ht in opened[1::3]:
            hs.close(); ht.close()
        for src, tgt in opened[2::3]:
            src.stop(); tgt.stop()

    @pytest.mark.parametrize(
        "do_sample",
        [False, pytest.param(True, marks=pytest.mark.slow)],
        ids=["greedy", "sampled"])
    def test_wire_migration_bitexact(self, fleet, do_sample):
        """The tentpole over real sockets: binary page frames out of
        the source host, restored on the target host, the client
        stream re-homed — bit-exact vs a never-migrated oracle with
        zero re-prefill and zero leaks on both processes' pools."""
        kw = dict(do_sample=do_sample)
        if do_sample:
            kw.update(temperature=0.7, top_k=8)
        src, tgt, hs, ht, rs, rt = fleet(src_faults=_throttle_fi(),
                                         **kw)
        oracle = make_stub_server(num_pages=17, **kw)
        oracle.start()
        got = []
        try:
            rid_o = oracle.submit(PROMPT, max_new_tokens=BUDGET, seed=5)
            rid = rs.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                            on_token=lambda r, t: got.extend(
                                int(x) for x in t))
            _wait(lambda: len(got) >= 6, msg="first streamed tokens")
            state, payloads = rs.migrate_out(rid)
            # client-truth delivery offset rides with the state so the
            # target's mirror starts exactly where this client stopped
            assert state.get("delivered") is not None
            new_rid = rt.migrate_in(
                state, payloads,
                on_token=lambda r, t: got.extend(int(x) for x in t))
            assert rs.migrate_finish(rid) is True
            out = rt.wait(new_rid, timeout=60)
            ref = oracle.wait(rid_o, timeout=60)
            np.testing.assert_array_equal(out, ref)
            if not do_sample:
                np.testing.assert_array_equal(
                    out, stub_tokens(PROMPT, BUDGET))
            _wait(lambda: len(got) >= BUDGET, timeout=10,
                  msg="stream drained")
            assert got == [int(t) for t in ref]
            assert tgt.stats["prefill_tokens"] == 0
            assert tgt.stats["admissions"] == 0
            assert src.stats["migrations"] == 1
            assert tgt.stats["migrated_in"] == 1
            for s in (src, tgt):
                assert s.pool_balance()[1] == 0
        finally:
            oracle.stop()

    def test_wire_checksum_mismatch_falls_back(self, fleet):
        """A payload corrupted between hosts fails the END-TO-END
        sha256 at restore (typed, over the wire) — the source aborts,
        resumes, and finishes the stream itself; zero leaks."""
        src, tgt, hs, ht, rs, rt = fleet(src_faults=_throttle_fi())
        got = []
        rid = rs.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                        on_token=lambda r, t: got.extend(
                            int(x) for x in t))
        _wait(lambda: len(got) >= 6, msg="first streamed tokens")
        state, payloads = rs.migrate_out(rid)
        tampered = [np.array(p) for p in payloads]
        tampered[0].flat[0] += 1.0
        with pytest.raises(MigrationError):
            rt.migrate_in(state, tampered)
        assert rs.migrate_abort(rid) is True
        np.testing.assert_array_equal(rs.wait(rid, timeout=60),
                                      stub_tokens(PROMPT, BUDGET))
        assert src.stats["migration_fallbacks"] == 1
        assert tgt.stats["migrated_in"] == 0
        for s in (src, tgt):
            assert s.pool_balance()[1] == 0

    def test_drop_storm_backfill_heals_token_stream(self, fleet):
        """The ``remote._on_tokens`` regression (satellite): a
        ``net.send`` drop storm on the HOST side eats token-push
        frames mid-stream; the client detects each gap and repairs it
        with ``fetch_tokens`` backfill from the host's stash — the
        delivered stream ends COMPLETE and exact, not truncated at the
        first hole."""
        storm = _StormFactory(seed=8)
        fi = FaultInjector(seed=8, enabled=False) \
            .on(NET_SEND, probability=1.0, error=storm)
        src, tgt, hs, ht, rs, rt = fleet(src_faults=fi)
        got = []
        rid = rs.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                        on_token=lambda r, t: got.extend(
                            int(x) for x in t))
        _wait(lambda: len(got) >= 4, msg="stream started")
        fi.arm()                       # the storm eats mid-stream pushes
        np.testing.assert_array_equal(rs.wait(rid, timeout=60),
                                      stub_tokens(PROMPT, BUDGET))
        _wait(lambda: len(got) >= BUDGET, timeout=15,
              msg="backfill healed the stream")
        assert got == [int(t) for t in stub_tokens(PROMPT, BUDGET)]
        assert storm.drops >= 1        # the storm actually tore frames

    def test_cut_in_callback_window_no_double_delivery(self):
        """The exactly-once seam (ISSUE 20 regression): the server
        fires on_token AFTER releasing its tick lock, so a cut landing
        in that window gathers ``streamed`` ahead of what the wire has
        delivered. ``migrate_out`` must wait for those in-flight pushes
        before snapshotting the client-truth ``delivered`` offset —
        otherwise the target re-streams tokens the source wire is
        about to deliver and the first tokens arrive twice."""
        src = make_stub_server(num_pages=17)
        tgt = make_stub_server(num_pages=17)
        hs = ReplicaHost(src, heartbeat_s=30).start()
        ht = ReplicaHost(tgt, heartbeat_s=30).start()
        rs = RemoteReplica(hs.address)
        rt = RemoteReplica(ht.address)
        tgt.start()
        got = []
        try:
            rid = rs.submit(PROMPT, max_new_tokens=BUDGET, seed=5,
                            on_token=lambda r, t: got.extend(
                                int(x) for x in t))
            # hold the callback flush: tokens land in ``emitted`` (and
            # bump ``streamed``) under the lock while the wire push
            # stays queued — exactly the window a first-token cut hits
            fire = src._fire_callbacks
            src._fire_callbacks = lambda: None
            while not any(st is not None and st.emitted
                          for st in src._slots):
                src.step()
            assert got == []           # nothing crossed the wire yet
            out = {}

            def cut():
                out["state"], out["payloads"] = rs.migrate_out(rid)

            th = threading.Thread(target=cut)
            th.start()
            time.sleep(0.15)           # the cut is inside its catch-up
            src._fire_callbacks = fire  # wait now: release the queued
            fire()                     # pushes
            th.join(timeout=10)
            assert not th.is_alive(), "migrate_out never returned"
            state = out["state"]
            # delivered caught up to server truth: the split point is
            # agreed, so nothing is delivered twice
            assert len(state["delivered"]) == state["streamed"] >= 1
            new_rid = rt.migrate_in(state, out["payloads"],
                                    on_token=lambda r, t: got.extend(
                                        int(x) for x in t))
            rs.migrate_finish(rid)
            np.testing.assert_array_equal(rt.wait(new_rid, timeout=60),
                                          stub_tokens(PROMPT, BUDGET))
            _wait(lambda: len(got) >= BUDGET, timeout=15,
                  msg="stream complete")
            assert got == [int(t) for t in stub_tokens(PROMPT, BUDGET)]
        finally:
            rs.close(); rt.close()
            hs.close(); ht.close()
            src.stop(); tgt.stop()


# ============================================= kill drill (real SIGKILL)
@pytest.mark.net
@pytest.mark.slow
@pytest.mark.skipif(not _loopback_available(),
                    reason="cannot bind a loopback socket here")
class TestMidMigrationKillDrill:
    @pytest.fixture
    def procs(self):
        spawned = []
        yield spawned
        for proc in spawned:
            if proc.is_alive():
                proc.kill()
            proc.join(10)

    def test_sigkill_target_falls_back_zero_leaks_one_flow(
            self, procs, tmp_path):
        """Mid-migration SIGKILL: the target PROCESS dies between the
        source's gather and the restore. The router degrades to
        fallback (``migration_fallbacks`` counts, ``migrations`` does
        not), the source resumes the paused slot and finishes the
        stream BIT-EXACT with zero failed requests and zero leaked
        pages — and the request's journey still renders as ONE
        connected flow across process boundaries in the fleet trace."""
        import json as _json
        import os as _os
        import signal as _signal

        from _remote_stub import make_slow_stub_server
        from paddle_tpu.inference.remote import spawn_replica_host
        from paddle_tpu.inference.router import ReplicaRouter

        server_kw = dict(max_slots=2, max_cache_len=64, page_size=8,
                         num_pages=17, tick_sleep_s=0.01)
        addrs = []
        for _ in range(2):
            proc, addr = spawn_replica_host(
                make_slow_stub_server, server_kw, heartbeat_s=0.05,
                start_server=True)
            procs.append(proc)
            addrs.append(addr)
        reps = [RemoteReplica(addr, call_timeout_s=2.0)
                for addr in addrs]
        router = ReplicaRouter(reps, policy="least_loaded",
                               journeys=True, recorder=True)
        got = []
        try:
            rid = router.submit(PROMPT, max_new_tokens=BUDGET,
                                on_token=lambda r, t: got.extend(
                                    int(x) for x in t))
            _wait(lambda: len(got) >= 6, timeout=120,
                  msg="first streamed tokens from the child")
            with router._lock:
                src_idx = router._routes[rid].idx
            victim = 1 - src_idx
            _os.kill(procs[victim].pid, _signal.SIGKILL)
            procs[victim].join(10)
            moved = router._migrate_live(src_idx)
            assert moved == 0
            assert router._stats["migration_fallbacks"] == 1
            assert router._stats["migrations"] == 0
            out = router.wait(rid, timeout=120)
            np.testing.assert_array_equal(out,
                                          stub_tokens(PROMPT, BUDGET))
            assert got == [int(t) for t in stub_tokens(PROMPT, BUDGET)]
            # zero leaks on the (live) source, measured over the wire
            bal = reps[src_idx].pool_balance()
            assert bal is not None and bal[1] == 0, f"leaked: {bal}"
            # the fallback is attributed on the journey...
            timeline = router.journey(rid)
            assert any(e["phase"] == "migrating"
                       and e.get("fallback") for e in timeline)
            # ...and the journey is ONE connected flow spanning the
            # router pid and the source child pid
            path = tmp_path / "fleet.json"
            router.export_fleet_trace(str(path))
            evs = _json.loads(path.read_text())["traceEvents"]
            flows = [e for e in evs if e.get("cat") == "journey"
                     and e.get("id") == f"r{rid}"]
            assert len(flows) >= 2
            assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
            assert len({e["pid"] for e in flows}) >= 2
        finally:
            router.stop(drain=False, timeout=20, stop_replicas=False)
            for rep in reps:
                rep.close()


# ===================================== seeded chaos storm determinism
@pytest.mark.chaos
@pytest.mark.slow
class TestMigrationChaosStorm:
    """A 25% storm over every ``net.*`` + ``migrate.*`` point the
    migration path crosses, driven SINGLE-THREADED (manual step(),
    socketpair wire) so the fault trace is exact: same seed => same
    trace => same tokens, different seed => different trace."""

    @staticmethod
    def _storm_run(seed):
        fi = FaultInjector(seed=seed) \
            .on(NET_SEND, probability=0.25, error=NetDrop) \
            .on(NET_RECV, probability=0.25, error=NetDrop) \
            .on(NET_PAGE_SEND, probability=0.25, error=NetDrop) \
            .on(MIGRATE_GATHER, probability=0.25) \
            .on(MIGRATE_RESTORE, probability=0.25)
        kw = dict(SERVER_KW)
        src = ContinuousBatchingServer(StubModel(), fault_injector=fi,
                                       **kw)
        tgt = ContinuousBatchingServer(StubModel(), fault_injector=fi,
                                       **kw)
        sa, sb = socket.socketpair()
        a = Connection(sa, fault_injector=fi, peer="src-host")
        b = Connection(sb, peer="tgt-host")
        b._faults = fi
        got = []
        budget = 24
        rid = src.submit(PROMPT, max_new_tokens=budget, seed=5,
                         on_token=lambda r, t: got.extend(
                             int(x) for x in t))

        def step_until(srv, pred, cap=4000):
            for _ in range(cap):
                if pred():
                    return
                srv.step()
            raise AssertionError("stepped past the cap")

        step_until(src, lambda: len(got) >= 6)
        carrier, wait_rid = src, rid
        for _ in range(8):                      # bounded storm retries
            try:
                state, payloads = src.migrate_out(rid)
            except InjectedFault:
                continue                        # gather chaos: slot
            #                                     untouched, try again
            try:
                lost = not a.send({"op": "migrate_in",
                                   "n": len(payloads)})
                for i, p in enumerate(payloads):
                    arr = np.ascontiguousarray(np.stack(p))
                    if not a.send_pages(
                            {"i": i, "shape": list(arr.shape),
                             "dtype": str(arr.dtype)},
                            arr.tobytes()):
                        lost = True
                frames = {}
                header = None
                while True:
                    try:
                        msg = b.recv(timeout=0.1)
                    except TimeoutError:
                        break
                    if "op" in msg:
                        header = msg
                    else:
                        frames[int(msg["i"])] = np.frombuffer(
                            msg["_payload"],
                            dtype=np.dtype(msg["dtype"])) \
                            .reshape(msg["shape"])
                if lost or header is None \
                        or len(frames) != len(payloads):
                    src.migrate_abort(rid)      # frame loss: fallback
                    continue
                new_rid = tgt.migrate_in(
                    state, [frames[i] for i in range(len(payloads))],
                    on_token=lambda r, t: got.extend(
                        int(x) for x in t))
            except (InjectedFault, MigrationError):
                src.migrate_abort(rid)          # restore chaos
                continue
            src.migrate_finish(rid)
            carrier, wait_rid = tgt, new_rid
            break
        step_until(carrier, lambda: len(got) >= budget)
        out = carrier.wait(wait_rid, timeout=5)
        assert src.pool_balance()[1] == 0
        assert tgt.pool_balance()[1] == 0
        a.close()
        b.close()
        return list(fi.trace), [int(t) for t in out], list(got)

    def test_same_seed_same_trace_same_tokens(self):
        t1, out1, got1 = self._storm_run(13)
        t2, out2, got2 = self._storm_run(13)
        t3, _, _ = self._storm_run(14)
        assert t1 == t2                      # identical fault traces
        assert out1 == out2 == got1 == got2  # identical streams
        assert t1 != t3                      # the seed actually steers
        assert out1 == [int(t) for t in stub_tokens(PROMPT, 24)]
        assert len(t1) >= 1                  # the storm actually fired


# ======================================== sharded gather/scatter parity
@pytest.mark.mesh
@pytest.mark.slow
@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 forced host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
class TestShardedMigration:
    @pytest.fixture(scope="class")
    def llama4(self):
        """llama with 4 kv heads (divisible by mp=2) — real sampling,
        so the restored PRNG chain is exercised for real (the stub's
        closed-form logits cannot distinguish a mis-primed key)."""
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=1,
                          num_heads=8, num_kv_heads=4,
                          intermediate_size=128, max_seq_len=128)
        pt.seed(21)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    @pytest.mark.parametrize("src_mp,tgt_mp", [(2, 1), (1, 2)],
                             ids=["mp2_to_mp1", "mp1_to_mp2"])
    def test_cross_topology_migration_bitexact(self, llama4, src_mp,
                                               tgt_mp):
        """Pages gathered per shard on an mp=2 mesh restore into a
        single-device pool bit-exactly, and vice versa: the wire
        payload is topology-neutral host arrays, so migration crosses
        tensor-parallel layouts without a re-prefill."""
        from jax.sharding import Mesh

        def mesh(n):
            return Mesh(np.array(jax.devices()[:n]), ("mp",)) \
                if n > 1 else None

        kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                  page_size=8, num_pages=24, do_sample=True,
                  temperature=0.8, top_k=20)
        src = ContinuousBatchingServer(llama4, mesh=mesh(src_mp), **kw)
        tgt = ContinuousBatchingServer(llama4, mesh=mesh(tgt_mp), **kw)
        oracle = ContinuousBatchingServer(llama4, **kw)
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, 256, (9,)).astype(np.int32)
        budget = 24
        got = []
        src.start(); tgt.start(); oracle.start()
        try:
            rid_o = oracle.submit(prompt, max_new_tokens=budget,
                                  seed=31)
            rid = src.submit(prompt, max_new_tokens=budget, seed=31,
                             on_token=_sink(got))
            _wait(lambda: len(got) >= 6, timeout=120,
                  msg="first streamed tokens")
            state, payloads = src.migrate_out(rid)
            new_rid = tgt.migrate_in(state, payloads,
                                     on_token=_sink(got))
            src.migrate_finish(rid)
            out = tgt.wait(new_rid, timeout=120)
            ref = oracle.wait(rid_o, timeout=120)
            np.testing.assert_array_equal(out, ref)
            assert tgt.stats["prefill_tokens"] == 0
            assert tgt.stats["admissions"] == 0
            for s in (src, tgt):
                assert s.pool_balance()[1] == 0
        finally:
            src.stop(); tgt.stop(); oracle.stop()

    @pytest.mark.parametrize("src_mp,tgt_mp", [(2, 1), (1, 2)],
                             ids=["mp2_to_mp1", "mp1_to_mp2"])
    def test_cross_topology_prefill_handoff_bitexact(self, llama4,
                                                     src_mp, tgt_mp):
        """The ISSUE-20 cut of the same drill: migrate a slot whose
        ``emitted`` is still EMPTY (mid-prefill) across tensor-parallel
        layouts — the target finishes the remaining prompt chunks and
        samples the first token from the restored seed, bit-exact vs
        the never-handed-off oracle, with only the unfinished tail
        re-prefilled."""
        from jax.sharding import Mesh

        def mesh(n):
            return Mesh(np.array(jax.devices()[:n]), ("mp",)) \
                if n > 1 else None

        kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                  page_size=8, num_pages=24, do_sample=True,
                  temperature=0.8, top_k=20,
                  prefill_tokens_per_tick=8)
        src = ContinuousBatchingServer(llama4, mesh=mesh(src_mp),
                                       role="prefill", **kw)
        tgt = ContinuousBatchingServer(llama4, mesh=mesh(tgt_mp),
                                       role="decode", **kw)
        oracle = ContinuousBatchingServer(llama4, **kw)
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, 256, (20,)).astype(np.int32)
        budget = 16
        got = []
        oracle.start()
        try:
            rid_o = oracle.submit(prompt, max_new_tokens=budget,
                                  seed=31)
            rid = src.submit(prompt, max_new_tokens=budget, seed=31,
                             on_token=_sink(got, dt=0))
            src.step()                   # admit + first chunk: 8 of 20
            state, payloads = src.migrate_out(rid)
            assert state["phase"] == "prefill"
            assert int(state["filled"]) == 8
            new_rid = tgt.migrate_in(state, payloads,
                                     on_token=_sink(got, dt=0))
            src.migrate_finish(rid)
            while tgt._busy_locked():
                tgt.step()
            out = tgt.wait(new_rid, timeout=120)
            ref = oracle.wait(rid_o, timeout=120)
            np.testing.assert_array_equal(out, ref)
            assert got == [int(t) for t in ref]
            assert tgt.stats["prefill_tokens"] == len(prompt) - 8
            assert tgt.stats["admissions"] == 1   # the TARGET activates
            for s in (src, tgt):
                assert s.pool_balance()[1] == 0
        finally:
            src.stop(); tgt.stop(); oracle.stop()


# ================================ prefill->decode handoff (ISSUE 20)
HANDOFF_KW = dict(SERVER_KW, prefill_tokens_per_tick=8)
LONG_PROMPT = (np.arange(1, 25, dtype=np.int32) % 13)   # 24 = 3 chunks
HBUDGET = 12         # 24-token prompt + 12 <= max_cache_len 64
SHORT_PROMPT = np.asarray([3, 1, 4], np.int32)


def _step_until_idle(*servers, cap=20000):
    for _ in range(cap):
        busy = False
        for srv in servers:
            if srv._busy_locked():
                srv.step()
                busy = True
        if not busy:
            return
    raise AssertionError("servers never went idle")


def _oracle_tokens(budget=HBUDGET, seed=5, prompt=None, **kw):
    """Single-replica never-handed-off reference stream."""
    oracle = ContinuousBatchingServer(StubModel(),
                                      **dict(HANDOFF_KW, **kw))
    rid = oracle.submit(LONG_PROMPT if prompt is None else prompt,
                        max_new_tokens=budget, seed=seed)
    _step_until_idle(oracle)
    return oracle.wait(rid, timeout=5)


class TestPrefillHandoff:
    """The empty-``emitted`` handoff matrix: migrating a slot that has
    not sampled its first token IS a prefill->decode handoff (the
    PR-18 refusal seam, lifted by ISSUE 20)."""

    @pytest.mark.parametrize("do_sample", [False, True],
                             ids=["greedy", "sampled"])
    def test_empty_emitted_handoff_bitexact(self, do_sample):
        """Mid-prefill migrate_out (emitted == []) restores on a decode
        specialist which finishes the remaining chunks and samples the
        first token from the restored seed — bit-exact vs the oracle,
        only the unfinished tail re-prefilled, zero leaks."""
        kw = dict(do_sample=do_sample)
        if do_sample:
            kw.update(temperature=0.7, top_k=8)
        src = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **dict(HANDOFF_KW, **kw))
        tgt = ContinuousBatchingServer(StubModel(), role="decode",
                                       **dict(HANDOFF_KW, **kw))
        got = []
        rid = src.submit(LONG_PROMPT, max_new_tokens=HBUDGET, seed=5,
                         on_token=_sink(got, dt=0))
        src.step(); src.step()          # admit + chunks 1,2: 16 of 24
        state, payloads = src.migrate_out(rid)
        assert state["phase"] == "prefill"
        assert state["emitted"] == [] or len(state["emitted"]) == 0
        assert int(state["filled"]) == 16
        assert len(payloads) == 2       # 16 written rows = 2 full pages
        new_rid = tgt.migrate_in(state, payloads,
                                 on_token=_sink(got, dt=0))
        src.migrate_finish(rid)
        _step_until_idle(tgt)
        out = tgt.wait(new_rid, timeout=5)
        ref = _oracle_tokens(**kw)
        np.testing.assert_array_equal(out, ref)
        assert got == [int(t) for t in ref]
        # zero RE-prefill: the target only ran the tokens the source
        # had not reached (24 - 16), never the handed-off 16
        assert src.stats["prefill_tokens"] == 16
        assert tgt.stats["prefill_tokens"] == len(LONG_PROMPT) - 16
        assert tgt.stats["admissions"] == 1   # the TARGET activates
        assert src.stats["migrations"] == 1
        assert tgt.stats["migrated_in"] == 1
        for s in (src, tgt):
            assert s.pool_balance()[1] == 0

    def test_staged_pipelined_handoff_bitexact(self):
        """The pipelined protocol end to end, deterministically
        step-driven: partial frames stream completed chunks while the
        source keeps prefilling; the closing pull carries only the
        unshipped tail; the commit launches decode — bit-exact, every
        page shipped exactly once."""
        src = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **HANDOFF_KW)
        tgt = ContinuousBatchingServer(StubModel(), role="decode",
                                       **HANDOFF_KW)
        got = []
        rid = src.submit(LONG_PROMPT, max_new_tokens=HBUDGET, seed=5,
                         on_token=_sink(got, dt=0))
        src.step()                           # chunk 1: 8 of 24 filled
        frag, payloads = src.migrate_out(rid, partial=True)
        assert frag["partial"] and frag["phase"] == "prefill"
        assert frag["base"] == 0 and len(payloads) == 1
        handle = tgt.migrate_in_begin(
            {"rid": int(rid), "ids": LONG_PROMPT,
             "prompt_len": len(LONG_PROMPT), "budget": HBUDGET,
             "seed": 5, "page_size": 8, "phase": "prefill"})
        assert tgt.migrate_in_pages(handle, 0, payloads,
                                    frag["sha256"]) == 1
        src.step()                           # chunk 2: 16 filled
        frag2, payloads2 = src.migrate_out(rid, partial=True)
        assert frag2["base"] == 1 and len(payloads2) == 1
        tgt.migrate_in_pages(handle, 1, payloads2, frag2["sha256"])
        # closing pull: everything from page 2 on (the incomplete
        # third page has nothing written yet -> zero tail payloads)
        state, tail = src.migrate_out(rid, from_page=2)
        assert state["base"] == 2 and tail == []
        new_rid = tgt.migrate_in_commit(handle, state, tail,
                                        on_token=_sink(got, dt=0))
        src.migrate_finish(rid)
        _step_until_idle(tgt)
        out = tgt.wait(new_rid, timeout=5)
        np.testing.assert_array_equal(out, _oracle_tokens())
        assert got == [int(t) for t in out]
        assert tgt.stats["prefill_tokens"] == len(LONG_PROMPT) - 16
        assert src.stats["handoff_pages_out"] == 2
        assert tgt.stats["handoff_pages_in"] == 2
        for s in (src, tgt):
            assert s.pool_balance()[1] == 0

    def test_refusal_matrix_typed(self):
        """Role and protocol refusals are typed ``MigrationError``s
        that leave both ends untouched: a prefill specialist refuses
        decode-phase admissions; a pipelined state (base > 0) refuses
        the one-shot ``migrate_in``; an unknown staging handle
        refuses page frames."""
        src = ContinuousBatchingServer(StubModel(), **HANDOFF_KW)
        pre = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **HANDOFF_KW)
        got = []
        rid = src.submit(PROMPT, max_new_tokens=HBUDGET, seed=5,
                         on_token=_sink(got, dt=0))
        for _ in range(50):                # well into decode
            src.step()
            if len(got) >= 4:
                break
        state, payloads = src.migrate_out(rid)
        assert state["phase"] == "decode"
        with pytest.raises(MigrationError, match="role 'prefill'"):
            pre.migrate_in(state, payloads)
        with pytest.raises(MigrationError, match="migrate_in_begin"):
            ContinuousBatchingServer(StubModel(), **HANDOFF_KW) \
                .migrate_in(dict(state, base=2), payloads)
        with pytest.raises(MigrationError, match="staged"):
            pre.migrate_in_pages(999, 0, payloads)
        assert pre.stats["migrated_in"] == 0
        assert pre.pool_balance()[1] == 0
        # the refused source resumes and finishes bit-exact
        assert src.migrate_abort(rid) is True
        _step_until_idle(src)
        np.testing.assert_array_equal(
            src.wait(rid, timeout=5),
            _oracle_tokens(prompt=PROMPT))
        assert src.pool_balance()[1] == 0

    def test_midprefill_abort_resumes_bitexact(self):
        """migrate_abort on a paused MID-PREFILL slot re-queues it on
        the prefill fifo exactly where it stopped — the source
        finishes the remaining chunks and the stream is bit-exact."""
        src = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **HANDOFF_KW)
        got = []
        rid = src.submit(LONG_PROMPT, max_new_tokens=HBUDGET, seed=5,
                         on_token=_sink(got, dt=0))
        src.step()
        state, _ = src.migrate_out(rid)
        assert state["phase"] == "prefill"
        assert src.migrate_abort(rid) is True
        _step_until_idle(src)
        np.testing.assert_array_equal(src.wait(rid, timeout=5),
                                      _oracle_tokens())
        assert src.stats["prefill_tokens"] == len(LONG_PROMPT)
        assert src.stats["migration_fallbacks"] == 1
        assert src.pool_balance()[1] == 0

    def test_staged_abort_leaks_nothing(self):
        """Aborting an open staging releases the placeholder's pages
        (no prefix-cache donation of garbage rows) and is
        idempotent."""
        tgt = ContinuousBatchingServer(StubModel(), role="decode",
                                       **HANDOFF_KW)
        free0 = tgt.pool_balance()[0]
        handle = tgt.migrate_in_begin(
            {"rid": 1, "ids": LONG_PROMPT,
             "prompt_len": len(LONG_PROMPT), "budget": HBUDGET,
             "seed": 5, "page_size": 8, "phase": "prefill"})
        assert tgt.pool_balance()[0] < free0      # pages reserved
        assert tgt.migrate_in_abort(handle) is True
        assert tgt.migrate_in_abort(handle) is False   # idempotent
        assert tgt.pool_balance()[0] == free0
        assert tgt.pool_balance()[1] == 0

    def _drive_router(self, router, reps, timeout=90):
        """Threaded-pump-aware drive: step serving replicas while the
        router's handoff pump runs in the background."""
        deadline = time.monotonic() + timeout
        idle = 0
        while time.monotonic() < deadline:
            router.poll()
            busy = False
            for rep in reps:
                if rep.health == "dead":
                    continue
                if rep.queue_depth() or rep.in_flight():
                    rep.step()
                    busy = True
            idle = 0 if busy else idle + 1
            if idle >= 3:
                return
            time.sleep(0.0005)
        raise AssertionError("router drive did not converge")

    def test_disaggregated_router_handoff_end_to_end(self):
        """placement="disaggregated" end to end: the long prompt lands
        on the prefill specialist, the pump hands it to the decode
        specialist (zero re-prefill), the journey crosses a "handoff"
        phase, and the short prompt bypasses the specialist
        entirely."""
        from paddle_tpu.inference.router import ReplicaRouter
        pre = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **HANDOFF_KW)
        dec = ContinuousBatchingServer(StubModel(), role="decode",
                                       **HANDOFF_KW)
        router = ReplicaRouter([pre, dec], placement="disaggregated",
                               disagg_prefill_min_tokens=16,
                               journeys=True, recorder=True)
        got = []
        rid = router.submit(LONG_PROMPT, max_new_tokens=HBUDGET,
                            seed=5, on_token=_sink(got))
        self._drive_router(router, [pre, dec])
        out = router.wait(rid, timeout=60)
        np.testing.assert_array_equal(out, _oracle_tokens())
        assert got == [int(t) for t in out]
        assert router.stats["handoffs"] == 1
        assert router.stats["handoff_fallbacks"] == 0
        assert dec.stats["prefill_tokens"] == 0       # zero re-prefill
        assert pre.stats["prefill_tokens"] == len(LONG_PROMPT)
        timeline = router.journey(rid)
        assert any(e["phase"] == "handoff" for e in timeline)
        # short prompts skip the specialist: decode-local, no handoff
        rid2 = router.submit(SHORT_PROMPT, max_new_tokens=4)
        self._drive_router(router, [pre, dec])
        np.testing.assert_array_equal(
            router.wait(rid2, timeout=30),
            _oracle_tokens(budget=4, seed=None, prompt=SHORT_PROMPT))
        assert router.stats["handoffs"] == 1          # unchanged
        assert router.stats["routed"] == [1, 1]       # short went
        #                                               decode-local
        for s in (pre, dec):
            assert s.pool_balance()[1] == 0

    def test_all_specialists_down_degrades_to_hybrid(self):
        """A dead prefill specialist does not strand long prompts:
        phase ordering degrades to any serving replica and the decode
        specialist serves the whole request itself."""
        from paddle_tpu.inference.router import ReplicaRouter
        pre = ContinuousBatchingServer(StubModel(), role="prefill",
                                       **HANDOFF_KW)
        dec = ContinuousBatchingServer(StubModel(), role="decode",
                                       **HANDOFF_KW)
        router = ReplicaRouter([pre, dec], placement="disaggregated",
                               disagg_prefill_min_tokens=16)
        pre.stop(drain=False)
        rid = router.submit(LONG_PROMPT, max_new_tokens=HBUDGET, seed=5)
        self._drive_router(router, [pre, dec])
        np.testing.assert_array_equal(router.wait(rid, timeout=60),
                                      _oracle_tokens())
        assert router.stats["routed"][1] == 1
        assert router.stats["handoffs"] == 0
        assert dec.stats["prefill_tokens"] == len(LONG_PROMPT)


class _PageStorm:
    """Capped ``net.page_send`` drop storm: a seeded 25% of page
    frames vanish mid-wire (up to ``max_drops``), the rest ride a
    pacing delay so the prefill stays stretched while the pump pulls
    partial batches."""

    def __init__(self, seed, p_drop=0.25, max_drops=4):
        self.rng = random.Random(seed)
        self.p_drop, self.max_drops = p_drop, max_drops
        self.drops = 0

    def __call__(self):
        if self.drops < self.max_drops \
                and self.rng.random() < self.p_drop:
            self.drops += 1
            return NetDrop("page storm")
        return _Throttle("pacing")


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.skipif(not _loopback_available(),
                    reason="cannot bind a loopback socket here")
class TestPartialHandoffStorm:
    def test_partial_frames_survive_page_send_storm(self):
        """Chunked partial-handoff frame ordering under a 25%
        ``net.page_send`` storm: dropped frames surface as holes in
        the pulled batch (never exceptions), holes are simply not
        forwarded, and the closing pull re-ships everything above the
        delivered contiguous prefix — the handoff still lands
        bit-exact with every page landing exactly once."""
        from _remote_stub import make_slow_stub_server
        storm = _PageStorm(seed=8)   # seeded to tear frames 1,3,5,6
        fi = FaultInjector(seed=8) \
            .on(NET_PAGE_SEND, probability=1.0, error=storm)
        kw = dict(max_slots=2, max_cache_len=96, page_size=8,
                  num_pages=24, prefill_tokens_per_tick=8)
        src = make_slow_stub_server(tick_sleep_s=0.03, role="prefill",
                                    **kw)
        tgt = make_slow_stub_server(tick_sleep_s=0.0, role="decode",
                                    **kw)
        hs = ReplicaHost(src, heartbeat_s=30,
                         fault_injector=fi).start()
        ht = ReplicaHost(tgt, heartbeat_s=30).start()
        rs, rt = RemoteReplica(hs.address), RemoteReplica(ht.address)
        src.start(); tgt.start()
        prompt = (np.arange(1, 41, dtype=np.int32) % 13)   # 5 pages
        budget = 8
        got = []
        collect = lambda r, t: got.extend(int(x) for x in t)  # noqa: E731
        try:
            assert rs.role == "prefill" and rt.role == "decode"
            rid = rs.submit(prompt, max_new_tokens=budget, seed=5,
                            on_token=collect)
            delivered = set()
            handle = None
            pulled_holes = 0
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    frag, payloads = rs.migrate_out(rid, partial=True)
                except MigrationError:
                    time.sleep(0.005)
                    continue
                if frag["phase"] != "prefill":
                    break
                if payloads:
                    if handle is None:
                        handle = rt.migrate_in_begin(
                            {"rid": int(rid), "ids": prompt,
                             "prompt_len": len(prompt),
                             "budget": budget, "seed": 5,
                             "page_size": 8, "phase": "prefill"})
                    base0 = int(frag["base"])
                    shas = frag["sha256"]
                    i = 0
                    while i < len(payloads):
                        if payloads[i] is None:
                            pulled_holes += 1
                            i += 1
                            continue
                        j = i
                        while j < len(payloads) \
                                and payloads[j] is not None:
                            j += 1
                        landed = rt.migrate_in_pages(
                            handle, base0 + i, payloads[i:j],
                            shas[i:j])
                        delivered.update(int(p) for p in landed)
                        i = j
                time.sleep(0.005)
            else:
                raise AssertionError("source never reached decode")
            k = 0
            while k in delivered:
                k += 1
            new_rid = None
            for _ in range(6):              # storm-bounded retries
                try:
                    state, tail = rs.migrate_out(rid, from_page=k)
                except MigrationError:
                    time.sleep(0.01)
                    continue
                if any(p is None for p in tail):
                    rs.migrate_abort(rid)
                    continue
                try:
                    if handle is not None:
                        new_rid = rt.migrate_in_commit(
                            handle, state, tail, on_token=collect)
                    else:
                        new_rid = rt.migrate_in(state, tail,
                                                on_token=collect)
                except MigrationError:
                    rs.migrate_abort(rid)
                    continue
                break
            assert new_rid is not None, "handoff never committed"
            rs.migrate_finish(rid)
            out = rt.wait(new_rid, timeout=60)
            ref = stub_tokens(prompt, budget)
            np.testing.assert_array_equal(out, ref)
            _wait(lambda: len(got) >= budget, timeout=15,
                  msg="stream drained")
            assert got == [int(t) for t in ref]
            assert storm.drops >= 1         # the storm actually tore
            assert tgt.stats["prefill_tokens"] == 0
            assert tgt.stats["admissions"] == 0
            for s in (src, tgt):
                assert s.pool_balance()[1] == 0
        finally:
            rs.close(); rt.close()
            hs.close(); ht.close()
            src.stop(); tgt.stop()


@pytest.mark.net
@pytest.mark.slow
@pytest.mark.skipif(not _loopback_available(),
                    reason="cannot bind a loopback socket here")
class TestPrefillSpecialistKillDrill:
    @pytest.fixture
    def procs(self):
        spawned = []
        yield spawned
        for proc in spawned:
            if proc.is_alive():
                proc.kill()
            proc.join(10)

    def test_sigkill_prefill_specialist_mid_handoff(self, procs,
                                                    tmp_path):
        """SIGKILL the prefill specialist PROCESS mid-prompt: the
        supervisor evacuates, the prompt requeues on the decode
        specialist via the normal path (degraded hybrid — it prefills
        itself) and finishes BIT-EXACT with zero lost requests, zero
        leaked pages on the surviving end, and the journey rendering
        as one connected flow across pids."""
        import json as _json
        import os as _os
        import signal as _signal

        from _remote_stub import make_slow_stub_server
        from paddle_tpu.inference.remote import spawn_replica_host
        from paddle_tpu.inference.router import ReplicaRouter

        base_kw = dict(max_slots=2, max_cache_len=96, page_size=8,
                       num_pages=24, tick_sleep_s=0.01,
                       prefill_tokens_per_tick=8)
        addrs = []
        for role in ("prefill", "decode"):
            proc, addr = spawn_replica_host(
                make_slow_stub_server, dict(base_kw, role=role),
                heartbeat_s=0.05, start_server=True)
            procs.append(proc)
            addrs.append(addr)
        reps = [RemoteReplica(addr, call_timeout_s=2.0)
                for addr in addrs]
        router = ReplicaRouter(reps, placement="disaggregated",
                               disagg_prefill_min_tokens=16,
                               journeys=True, recorder=True)
        prompt = (np.arange(1, 41, dtype=np.int32) % 13)
        budget = 16
        got = []
        try:
            _wait(lambda: reps[0].role == "prefill"
                  and reps[1].role == "decode", timeout=60,
                  msg="roles ride the heartbeat digests")
            router.start(poll_interval=0.02, start_replicas=False)
            rid = router.submit(prompt, max_new_tokens=budget,
                                on_token=lambda r, t: got.extend(
                                    int(x) for x in t))
            with router._lock:
                assert router._routes[rid].idx == 0   # specialist won
            time.sleep(0.04)             # mid-prompt, pump possibly
            #                              mid-partial-batch
            _os.kill(procs[0].pid, _signal.SIGKILL)
            procs[0].join(10)
            out = router.wait(rid, timeout=120)
            ref = stub_tokens(prompt, budget)
            np.testing.assert_array_equal(out, ref)
            _wait(lambda: len(got) >= budget, timeout=15,
                  msg="stream drained")
            assert got == [int(t) for t in ref]
            # zero leaks on the surviving decode end (any staged
            # placeholder from a mid-flight pump was aborted)
            _wait(lambda: (reps[1].pool_balance() or (0, 1))[1] == 0,
                  timeout=30, msg="decode pool settles to zero live")
            # one connected flow across the router pid and >= 1 child
            path = tmp_path / "fleet.json"
            router.export_fleet_trace(str(path))
            evs = _json.loads(path.read_text())["traceEvents"]
            flows = [e for e in evs if e.get("cat") == "journey"
                     and e.get("id") == f"r{rid}"]
            assert len(flows) >= 2
            assert flows[0]["ph"] == "s" and flows[-1]["ph"] == "f"
            assert len({e["pid"] for e in flows}) >= 2
        finally:
            router.stop(drain=False, timeout=20, stop_replicas=False)
            for rep in reps:
                rep.close()
