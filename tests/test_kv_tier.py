"""Tiered KV cache (ISSUE 17): host-RAM spill under the prefix cache.

Three layers of coverage:

- ``HostTier`` unit tests: checksummed put/get round trips, corrupt
  payload = miss-plus-counter, byte accounting, budget validation, and
  the ``tier.spill`` / ``tier.restore`` fault points changing no state.
- ``PrefixCache`` + tier against a bare ``PagedKVCache``: eviction
  demotes bottom-up (device-leaf first), host nodes stay lookup-able
  with their sketch fingerprints, spill-fault falls back to a clean
  drop, donation adopts host nodes without a restore read, and the
  host byte budget evicts LRU leaves for real at the bottom.
- Server-level tests on the StubModel double and a real llama:
  spill -> restore round trips are BIT-EXACT (restored page contents
  asserted, plus greedy and seeded-sampled token parity vs a
  never-evicted oracle, including restore -> preempt -> replay), a
  corrupted host buffer is a miss plus ``kv_host_restore_corrupt_total``
  (never a failure), spill/restore are priced via the cost catalog but
  never counted as tick dispatches, and a chaos storm at 30% on the
  tier points leaves zero pages leaked in EITHER tier with same-seed
  identical traces. An mp=2 mesh restore (per-shard gather/scatter)
  closes the sharded-pool satellite.
"""
import numpy as np
import pytest

import jax

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.kv_tier import HostTier
from paddle_tpu.models.generation import pool_heads
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.reliability import (CallbackError, CircuitBreaker,
                                    FaultInjector, InjectedFault,
                                    RetryPolicy, faults)
from paddle_tpu.telemetry import (CostCatalog, MetricRegistry,
                                  ServerTelemetry)

PG = 4
PAGE_NBYTES = 64          # stub pool: K+V rows of one page, float32


def _arrs(x=1.0):
    return [np.full((1, PG, 1, 2), x, np.float32),
            np.full((1, PG, 1, 2), x + 0.5, np.float32)]


def _tiered_cache(num_pages=17, budget=None, tier_injector=None):
    kv = PagedKVCache(num_pages=num_pages, page_size=PG, max_slots=4,
                      pages_per_slot=8)
    tier = HostTier(budget_bytes=budget, fault_injector=tier_injector)
    cache = PrefixCache(kv, host_tier=tier,
                        spill=lambda page: _arrs(float(page)))
    return cache, kv, tier


def _donate(cache, kv, ids):
    ids = np.asarray(ids, np.int32)
    pages = kv.alloc(-(-len(ids) // PG))
    cache.donate(ids, pages, len(ids))
    return pages


# --------------------------------------------------------------------------
# HostTier unit contracts
# --------------------------------------------------------------------------
class TestHostTierUnit:
    def test_put_get_round_trip_and_accounting(self):
        tier = HostTier()
        entry = tier.put(_arrs())
        assert tier.entries == 1
        assert tier.bytes_used == entry.nbytes == PAGE_NBYTES
        assert tier.spilled_pages_total == 1
        back = tier.get(entry)
        for a, b in zip(back, _arrs()):
            np.testing.assert_array_equal(a, b)
        assert tier.restored_pages_total == 1
        tier.discard(entry)
        assert tier.entries == 0 and tier.bytes_used == 0
        assert tier.evicted_pages_total == 0     # promotion, not eviction

    def test_corrupt_payload_is_miss_plus_counter(self):
        tier = HostTier()
        entry = tier.put(_arrs())
        entry.payload[0][0, 0, 0, 0] += 1.0      # flip a buffer byte
        assert tier.get(entry) is None
        assert tier.restore_corrupt_total == 1
        assert tier.restored_pages_total == 0

    def test_budget_validation_and_over_budget(self):
        with pytest.raises(ValueError):
            HostTier(budget_bytes=-1)
        tier = HostTier(budget_bytes=PAGE_NBYTES)
        e1 = tier.put(_arrs())
        assert not tier.over_budget()
        tier.put(_arrs(2.0))
        assert tier.over_budget()
        tier.discard(e1, evicted=True)
        assert not tier.over_budget()
        assert tier.evicted_pages_total == 1
        assert HostTier(budget_bytes=None).over_budget() is False

    def test_spill_fault_raises_before_any_state_change(self):
        fi = FaultInjector(seed=3).on(faults.TIER_SPILL, probability=1.0)
        tier = HostTier(fault_injector=fi)
        with pytest.raises(InjectedFault):
            tier.put(_arrs())
        assert tier.entries == 0 and tier.bytes_used == 0
        assert tier.spilled_pages_total == 0

    def test_restore_fault_raises_before_the_read(self):
        fi = FaultInjector(seed=3).on(faults.TIER_RESTORE, probability=1.0)
        fi.disarm()
        tier = HostTier(fault_injector=fi)
        entry = tier.put(_arrs())
        fi.arm()
        with pytest.raises(InjectedFault):
            tier.get(entry)
        assert tier.restored_pages_total == 0
        assert tier.entries == 1                 # run stays spilled


# --------------------------------------------------------------------------
# PrefixCache over the tier: unified radix tree, demotion, budget
# --------------------------------------------------------------------------
class TestTieredRadixTree:
    def test_evict_demotes_leaf_first_and_lookup_stays_unified(self):
        cache, kv, tier = _tiered_cache()
        ids = np.arange(12, dtype=np.int32)      # 3 full pages
        _donate(cache, kv, ids)
        free0 = kv.free_pages()
        assert cache.evict(2) == 2
        # demotion, not drop: device pages freed, nodes kept as host
        assert kv.free_pages() == free0 + 2
        assert cache.cached_pages == 1 and cache.host_pages == 2
        assert tier.entries == 2 and tier.spilled_pages_total == 2
        assert cache.evicted_pages_total == 0    # nothing truly dropped
        m = cache.lookup(ids, 12)
        assert m.tokens == 12 and len(m.nodes) == 3
        assert m.hot_len() == 1                  # hot prefix / host suffix
        assert m.nodes[0].page is not None
        assert all(n.page is None and n.host is not None
                   for n in m.nodes[1:])
        # spilled runs keep their sketch fingerprints (router affinity
        # covers the host tier for free)
        cache.flush_sketch()
        assert {n.fp for n in m.nodes} <= set(cache.sketch())
        assert cache.stats()["host_pages"] == 2

    def test_node_run_stops_at_first_host_node(self):
        cache, kv, tier = _tiered_cache()
        ids = np.arange(12, dtype=np.int32)
        _donate(cache, kv, ids)
        cache.evict(2)
        run = cache.node_run(ids)
        assert len(run) == 1 and run[0].page is not None

    def test_spill_fault_falls_back_to_clean_drop(self):
        fi = FaultInjector(seed=5).on(faults.TIER_SPILL, probability=1.0)
        cache, kv, tier = _tiered_cache(tier_injector=fi)
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        free0 = kv.free_pages()
        assert cache.evict(1) == 1
        # the device page is freed either way; the tier saw no state
        assert kv.free_pages() == free0 + 1
        assert cache.host_pages == 0 and tier.entries == 0
        assert cache.cached_pages == 1
        assert cache.evicted_pages_total == 1

    def test_drop_subtree_releases_both_tiers(self):
        cache, kv, tier = _tiered_cache()
        ids = np.arange(12, dtype=np.int32)
        _donate(cache, kv, ids)
        cache.evict(2)
        m = cache.lookup(ids, 12)
        released = cache.drop_subtree(m.nodes[0])
        assert released == 1                     # the one hot page
        assert cache.cached_pages == 0 and cache.host_pages == 0
        assert tier.entries == 0 and tier.bytes_used == 0
        assert tier.evicted_pages_total == 2
        assert kv.used_pages() == 0
        assert cache.lookup(ids, 12) is None
        cache.flush_sketch()
        assert not cache.sketch()

    def test_host_budget_evicts_lru_leaves_for_real(self):
        cache, kv, tier = _tiered_cache(budget=PAGE_NBYTES)
        ids = np.arange(12, dtype=np.int32)
        _donate(cache, kv, ids)
        cache.evict(3)
        # three demotions, then the budget forgets the two LRU leaves
        assert tier.spilled_pages_total == 3
        assert tier.entries == 1 and tier.bytes_used == PAGE_NBYTES
        assert tier.evicted_pages_total == 2
        assert cache.host_pages == 1
        m = cache.lookup(ids, 12)
        assert len(m.nodes) == 1 and m.nodes[0].host is not None

    def test_donate_adopts_host_nodes_without_a_restore_read(self):
        cache, kv, tier = _tiered_cache()
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        cache.evict(2)
        assert cache.host_pages == 2
        _donate(cache, kv, ids)                  # a slot recomputed it
        assert cache.host_pages == 0 and cache.cached_pages == 2
        assert tier.entries == 0
        assert tier.restored_pages_total == 0    # free promotion
        assert cache.dedup_pages_total == 0
        assert kv.used_pages() == 2


# --------------------------------------------------------------------------
# Server level: spill/restore round trips on the Stub double
# --------------------------------------------------------------------------
def _tier_srv(**kw):
    kw.setdefault("max_slots", 1)
    kw.setdefault("max_cache_len", 32)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 6)                # 5 usable: tight
    kw.setdefault("host_tier", HostTier())
    return ContinuousBatchingServer(StubModel(), **kw)


A8 = np.arange(8, dtype=np.int32)
B8 = (np.arange(8, dtype=np.int32) + 8) % 16
C8 = np.asarray([5, 5, 5, 5, 9, 9, 9, 9], np.int32)


def _spill_A(srv):
    """Serve A, then fill the pool with B and C so A's pages demote."""
    for p in (A8, B8, C8):
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 4))


def _ext_A(n=2):
    """A multi-turn prompt EXTENDING A's stored history (prompt +
    generated prefix + the new turn) — an identical prompt can match at
    most T-1 tokens, so only an extension reaches the host suffix."""
    return np.concatenate([A8, stub_tokens(A8, 4)[:n],
                           np.asarray([1, 2], np.int32)])


class TestHostTierServer:
    def test_spill_restore_round_trip_bit_exact(self):
        tele = ServerTelemetry(registry=MetricRegistry())
        srv = _tier_srv(telemetry=tele)
        tier = srv.host_tier
        _spill_A(srv)
        assert tier.spilled_pages_total == 2     # A's prompt pages demoted
        assert srv._prefix.host_pages == 2
        # the returning session's next turn restores through the
        # normal admit path and the tokens match the never-evicted
        # oracle exactly
        ext = _ext_A()
        rid = srv.submit(ext, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid],
                                      stub_tokens(ext, 4))
        assert tier.restored_pages_total == 2
        assert srv.stats["prefix_auto_hit_tokens"] >= 8
        # restored PAGE CONTENTS: the stub prefill writes token values
        # into cache rows, so the shared pages must hold A's tokens —
        # proof the payload round-tripped bit-exact, not just the ids
        m = srv._prefix.lookup(ext, 8)
        assert m is not None and m.hot_len() == len(m.nodes) == 2
        pool_k = pool_heads(np.asarray(srv._caches["pool"]["k"]),
                            StubModel.H)
        for i, nd in enumerate(m.nodes):
            np.testing.assert_array_equal(
                pool_k[0, nd.page, :, 0, 0],
                ext[i * 4:(i + 1) * 4].astype(np.float32))
        # balance + telemetry: host residency visible everywhere
        bal = srv.pool_balance()
        assert bal.host == srv._prefix.host_pages == tier.entries
        assert bal.host_bytes == tier.bytes_used
        free, live, pinned, cached = bal
        assert live == 0
        assert free + pinned + cached == srv._kv.num_pages - 1
        reg = tele.registry
        assert reg.get("kv_host_spilled_pages_total").value \
            == tier.spilled_pages_total
        assert reg.get("kv_host_restored_pages_total").value == 2
        assert reg.get("kv_pool_pages").labels(state="host").value \
            == srv._prefix.host_pages
        assert reg.get("serving_restore_seconds").count >= 1
        occ = srv._kv.occupancy(host_tier=srv._host)
        assert occ["host_tier"]["entries"] == tier.entries

    def test_corrupt_host_buffer_is_miss_plus_counter_never_failure(self):
        tele = ServerTelemetry(registry=MetricRegistry())
        srv = _tier_srv(telemetry=tele)
        tier = srv.host_tier
        _spill_A(srv)
        full = np.concatenate([A8, stub_tokens(A8, 4)])
        m = srv._prefix.lookup(full, 12)
        assert m.hot_len() == 0
        entry = m.nodes[0].host
        rotten = [a.copy() for a in entry.payload]
        rotten[0][0, 0, 0, 0] += 1.0                    # rot the buffer
        entry.payload = tuple(rotten)
        ext = _ext_A()
        rid = srv.submit(ext, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid],
                                      stub_tokens(ext, 4))
        assert tier.restore_corrupt_total == 1
        assert tele.registry.get("kv_host_restore_corrupt_total").value \
            == 1
        # the corrupt run (and its all-host subtree) left both tiers
        assert srv._prefix.lookup(full, 12) is None \
            or srv._prefix.lookup(full, 12).nodes[0].host is None
        bal = srv.pool_balance()
        assert bal.host == tier.entries

    def test_host_tier_bytes_kwarg_bounds_the_tier(self):
        srv = _tier_srv(host_tier=None, host_tier_bytes=PAGE_NBYTES)
        tier = srv.host_tier
        assert isinstance(tier, HostTier)
        assert tier.budget_bytes == PAGE_NBYTES
        _spill_A(srv)
        # two demotions but only one page of budget: the LRU host
        # leaf fell off the bottom of the hierarchy for real
        assert tier.spilled_pages_total == 2
        assert tier.entries == 1
        assert tier.bytes_used <= PAGE_NBYTES
        assert tier.evicted_pages_total == 1
        assert srv.pool_balance().host == 1

    def test_disabled_tier_is_structurally_free(self):
        srv = _tier_srv(host_tier=HostTier(enabled=False))
        assert srv._host is None
        assert srv._prefix._tier is None
        _spill_A(srv)
        assert srv.host_tier.spilled_pages_total == 0
        assert srv._prefix.host_pages == 0
        assert srv.pool_balance().host == 0
        # and the default server has no tier at all
        assert ContinuousBatchingServer(
            StubModel(), max_slots=1, max_cache_len=32,
            cache_backend="paged", page_size=4).host_tier is None

    def test_dense_backend_rejects_the_tier(self):
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatchingServer(StubModel(), max_slots=1,
                                     max_cache_len=32, host_tier=True)

    def test_spill_restore_priced_but_never_tick_dispatches(self):
        """Satellite 1: ``page_spill``/``page_restore`` ride the cost
        catalog as 2x-bytes-moved transfers and NEVER count against
        ``serving_tick_dispatches`` / ``server_dispatches_total``."""
        tele = ServerTelemetry(registry=MetricRegistry())
        cat = CostCatalog(registry=tele.registry)
        srv = _tier_srv(telemetry=tele, costs=cat)
        tier = srv.host_tier
        _spill_A(srv)
        rid = srv.submit(_ext_A(), max_new_tokens=4)
        srv.run()[rid]
        cat.flush_tick()
        tot = cat.totals()
        row = PAGE_NBYTES // PG                  # K+V bytes per token row
        assert tot["page_spill"]["hbm_bytes"] \
            == 2 * tier.spilled_pages_total * PG * row
        assert tot["page_restore"]["hbm_bytes"] \
            == 2 * tier.restored_pages_total * PG * row
        assert tot["page_spill"]["flops"] == 0.0
        assert tot["page_restore"]["flops"] == 0.0
        disp = tele.registry.get("server_dispatches_total")._children
        assert not any("page_spill" in str(k) or "page_restore" in str(k)
                       for k in disp)

    def test_postmortem_freezes_host_counts(self):
        srv = _tier_srv(recorder=True)
        _spill_A(srv)
        srv.kill(timeout=5.0)
        pm = srv.postmortems()[-1]
        assert pm["pool_balance"]["host"] == srv._prefix.host_pages
        assert pm["pool_balance"]["host_bytes"] \
            == srv.host_tier.bytes_used
        assert pm["block_table"]["host_tier"]["entries"] \
            == srv.host_tier.entries


# --------------------------------------------------------------------------
# Chaos: 30% storms over tier.spill / tier.restore
# --------------------------------------------------------------------------
@pytest.mark.chaos
class TestTierChaos:
    def _injector(self, seed):
        return (FaultInjector(seed=seed)
                .on(faults.PREFILL, probability=0.15)
                .on(faults.DECODE_TICK, probability=0.1)
                .on(faults.PAGE_ALLOC, probability=0.1)
                .on(faults.PREFIX_EVICT, probability=0.2)
                .on(faults.PREFIX_DONATE, probability=0.2)
                .on(faults.TIER_SPILL, probability=0.3)
                .on(faults.TIER_RESTORE, probability=0.3))

    def _srv(self, fi, **kw):
        kw.setdefault("max_slots", 2)
        kw.setdefault("max_cache_len", 32)
        kw.setdefault("cache_backend", "paged")
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 8)            # 7 usable: pressure
        kw.setdefault("host_tier",
                      HostTier(budget_bytes=8 * PAGE_NBYTES))
        kw.setdefault("retry_policy", RetryPolicy(base_delay_s=0.0,
                                                  jitter=0.0))
        kw.setdefault("breaker", CircuitBreaker(failure_threshold=10_000))
        return ContinuousBatchingServer(StubModel(), fault_injector=fi,
                                        **kw)

    def _drive(self, srv, max_ticks=5000):
        ticks = 0
        while True:
            with srv._lock:
                busy = srv._busy_locked()
            if not busy:
                return
            try:
                srv.step()
            except CallbackError:
                pass
            except Exception:
                pass
            ticks += 1
            assert ticks < max_ticks, "chaos drive did not converge"

    def _workload(self, seed=5, n=12):
        """DISTINCT per-user prompts (a shared system prefix dedups
        into two pages and the pool never runs short): each one
        donates its own page run, so the storm actually evicts."""
        rng = np.random.default_rng(seed)
        return [rng.integers(0, 16, (int(k),)).astype(np.int32)
                for k in rng.integers(8, 14, (n,))]

    def _run_storm(self, fi, srv):
        """Two phases: fill the tree under pressure, then come back
        with EXTENDING multi-turn prompts so restores are attempted."""
        prompts = self._workload()
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        self._drive(srv)
        exts = []
        for p in prompts[:8]:
            full = np.concatenate([p, stub_tokens(p, 4)])
            exts.append(np.concatenate(
                [full[:len(p) + 2],
                 np.asarray([int(p[0]) % 16, 3], np.int32)]))
        rids += [srv.submit(e, max_new_tokens=4) for e in exts]
        self._drive(srv)
        return prompts + exts, rids

    def test_tier_storm_zero_leaks_in_both_tiers(self):
        fi = self._injector(seed=606)
        srv = self._srv(fi)
        tier = srv.host_tier
        prompts, rids = self._run_storm(fi, srv)
        outs = srv._results
        served = 0
        for rid, p in zip(rids, prompts):
            if rid in outs:
                served += 1
                np.testing.assert_array_equal(outs[rid],
                                              stub_tokens(p, 4))
        assert served > 0
        assert fi.fired(faults.TIER_SPILL) > 0, "spill chaos idle"
        assert fi.fired(faults.TIER_RESTORE) \
            + tier.restored_pages_total > 0, "restore path idle"
        # device pool balanced: host nodes hold NO device page, so the
        # 4-tuple still sums to the usable pool
        bal = srv.pool_balance()
        free, live, pinned, cached = bal
        assert live == 0, f"leaked {live} device pages"
        assert free + pinned + cached == srv._kv.num_pages - 1
        # host tier balanced: tree view == tier accounting, budget held
        assert bal.host == srv._prefix.host_pages == tier.entries
        assert bal.host_bytes == tier.bytes_used \
            == tier.entries * PAGE_NBYTES
        assert tier.bytes_used <= tier.budget_bytes
        assert tier.evicted_pages_total > 0, "host LRU bottom idle"

    def test_same_seed_identical_trace_and_tier_state(self):
        def run_once():
            fi = self._injector(seed=4242)
            srv = self._srv(fi)
            self._run_storm(fi, srv)
            results = {r: tuple(int(x) for x in v)
                       for r, v in srv._results.items()}
            fails = {r: type(e).__name__
                     for r, e in srv.failures.items()}
            return (fi.trace, results, fails, srv.pool_balance(),
                    srv._prefix.stats(), srv.host_tier.stats())

        a, b = run_once(), run_once()
        assert a == b
        assert any(pt in (faults.TIER_SPILL, faults.TIER_RESTORE)
                   for pt, _ in a[0]), "deterministic run hit no tier"


# --------------------------------------------------------------------------
# Real-model parity: a restored run is bit-exact with a never-evicted one
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def llama():
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(21)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _llama_kw(**kw):
    base = dict(max_slots=1, max_cache_len=64, cache_backend="paged",
                page_size=8)
    base.update(kw)
    return base


def _llama_session(oracle, tiered, prompts, ext_turn, n_new, seeds=None):
    """Drive the SAME multi-turn session through a never-evicted oracle
    and a tight tiered server: prompts serve in order (spilling the
    first one's history on the tiered side), then the first session
    returns with ``ext_turn`` new tokens appended to its full history.
    Every request must be bit-identical across the pair."""
    seeds = seeds or [None] * (len(prompts) + 1)
    hist = None
    for i, p in enumerate(prompts):
        ra = oracle.submit(p, max_new_tokens=n_new, seed=seeds[i])
        rb = tiered.submit(p, max_new_tokens=n_new, seed=seeds[i])
        oa, ob = oracle.run()[ra], tiered.run()[rb]
        np.testing.assert_array_equal(oa, ob)
        if i == 0:
            hist = np.concatenate([p, np.asarray(oa, np.int32)])
    ext = np.concatenate([hist, ext_turn])
    ra = oracle.submit(ext, max_new_tokens=n_new, seed=seeds[-1])
    rb = tiered.submit(ext, max_new_tokens=n_new, seed=seeds[-1])
    np.testing.assert_array_equal(oracle.run()[ra], tiered.run()[rb])


class TestLlamaTieredParity:
    # tier-1 budget (the 870 s wall): the seeded-sampled drill below is
    # the in-budget canary; the greedy + preempt halves and the mesh
    # class run under `-m slow` with the other heavy llama e2e parity
    @pytest.mark.slow
    def test_greedy_restore_parity(self, llama):
        """The acceptance drill, greedy half: session A's history is
        spilled by three follow-up sessions, then its next turn
        restores it — tokens bit-identical to a pool that never
        evicted anything."""
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 256, (16,)).astype(np.int32)
                   for _ in range(4)]
        oracle = ContinuousBatchingServer(llama,
                                          **_llama_kw(num_pages=24))
        tiered = ContinuousBatchingServer(
            llama, **_llama_kw(num_pages=7, host_tier=HostTier()))
        _llama_session(oracle, tiered, prompts,
                       rng.integers(0, 256, (3,)).astype(np.int32),
                       n_new=4)
        tier = tiered.host_tier
        assert tier.spilled_pages_total > 0, "pool never spilled"
        assert tier.restored_pages_total >= 2, "turn 2 never restored"
        assert oracle.host_tier is None

    def test_seeded_sampled_restore_parity(self, llama):
        """The sampled half: per-request PRNG chains survive the spill
        -> restore detour — seeded sampling through a restored prefix
        is bit-identical to the never-evicted oracle."""
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 256, (16,)).astype(np.int32)
                   for _ in range(4)]
        kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9)
        oracle = ContinuousBatchingServer(
            llama, **_llama_kw(num_pages=24, **kw))
        tiered = ContinuousBatchingServer(
            llama, **_llama_kw(num_pages=7, host_tier=HostTier(), **kw))
        _llama_session(oracle, tiered, prompts,
                       rng.integers(0, 256, (3,)).astype(np.int32),
                       n_new=4, seeds=[101, 102, 103, 104, 105])
        assert tiered.host_tier.restored_pages_total >= 2

    @pytest.mark.slow
    def test_restore_then_preempt_then_replay_stays_bit_exact(self, llama):
        """Restore -> preempt -> replay: the restored session and a
        rival admit optimistically into a pool too small for both;
        the loser is preempted and replayed. Tokens still match the
        never-evicted oracle bit-for-bit."""
        rng = np.random.default_rng(7)
        # session A keeps a small footprint (its turn 2 must co-admit
        # with the rival); the fat fillers spill A's history in phase 1
        prompts = [rng.integers(0, 256, (8,)).astype(np.int32)] + [
            rng.integers(0, 256, (16,)).astype(np.int32)
            for _ in range(3)]
        oracle = ContinuousBatchingServer(
            llama, **_llama_kw(num_pages=24, max_slots=2))
        tiered = ContinuousBatchingServer(
            llama, **_llama_kw(num_pages=7, max_slots=2,
                               host_tier=HostTier(),
                               admission="optimistic",
                               headroom_pages=1))
        hist = None
        for i, p in enumerate(prompts):
            ra = oracle.submit(p, max_new_tokens=6)
            rb = tiered.submit(p, max_new_tokens=6)
            oa, ob = oracle.run()[ra], tiered.run()[rb]
            np.testing.assert_array_equal(oa, ob)
            if i == 0:
                hist = np.concatenate([p, np.asarray(oa, np.int32)])
        assert tiered.host_tier.spilled_pages_total > 0
        # turn 2 of session A races a fresh rival for the tiny
        # pool — the rival admits first (small prompt, small
        # footprint), then both optimistic slots grow into the same
        # exhausted pool and one gets preempted and replayed
        ext = np.concatenate(
            [hist, rng.integers(0, 256, (3,)).astype(np.int32)])
        rival = rng.integers(0, 256, (8,)).astype(np.int32)
        subs = [(rival, 12), (ext, 12)]
        ra = [oracle.submit(p, max_new_tokens=n) for p, n in subs]
        rb = [tiered.submit(p, max_new_tokens=n) for p, n in subs]
        oa, ob = oracle.run(), tiered.run()
        for x, y in zip(ra, rb):
            np.testing.assert_array_equal(oa[x], ob[y])
        assert tiered.host_tier.restored_pages_total >= 1
        assert tiered.pool_balance().preemptions >= 1, \
            "pool never preempted — shrink num_pages"


# --------------------------------------------------------------------------
# Sharded pool (mp=2): per-shard spill gathers / restore scatters
# --------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.mesh
@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs forced host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
class TestShardedTier:
    def test_mp2_spill_restore_bit_exact_full_width_payload(self):
        """Satellite 2: on a kv-head-sharded pool the spill gather goes
        per shard (slices concatenated to full head width in the host
        payload) and the restore scatter lays the payload back against
        the pool's own sharding — tokens bit-identical to a
        single-device never-evicted oracle."""
        from jax.sharding import Mesh

        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=1,
                          num_heads=8, num_kv_heads=4,
                          intermediate_size=128, max_seq_len=128)
        pt.seed(21)
        model = LlamaForCausalLM(cfg)
        model.eval()
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, 256, (16,)).astype(np.int32)
                   for _ in range(4)]
        oracle = ContinuousBatchingServer(model,
                                          **_llama_kw(num_pages=24))
        tiered = ContinuousBatchingServer(
            model, mesh=mesh,
            **_llama_kw(num_pages=7, host_tier=HostTier()))
        hist = None
        for i, p in enumerate(prompts):
            ra = oracle.submit(p, max_new_tokens=4)
            rb = tiered.submit(p, max_new_tokens=4)
            oa, ob = oracle.run()[ra], tiered.run()[rb]
            np.testing.assert_array_equal(oa, ob)
            if i == 0:
                hist = np.concatenate([p, np.asarray(oa, np.int32)])
        tier = tiered.host_tier
        assert tier.spilled_pages_total > 0
        # the host payload carries the FULL kv-head width — the
        # per-shard gather concatenated both devices' slices
        m = tiered._prefix.lookup(hist, len(hist))
        assert m is not None
        spilled = [n for n in m.nodes if n.host is not None]
        assert spilled
        assert spilled[0].host.payload[0].shape == (1, 8, 4, 8)
        # turn 2: restore through the sharded scatter, bit-exact
        ext = np.concatenate(
            [hist, rng.integers(0, 256, (3,)).astype(np.int32)])
        ra = oracle.submit(ext, max_new_tokens=4)
        rb = tiered.submit(ext, max_new_tokens=4)
        np.testing.assert_array_equal(oracle.run()[ra],
                                      tiered.run()[rb])
        assert tier.restored_pages_total >= 2
