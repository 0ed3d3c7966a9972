"""Automatic prefix caching (ISSUE 5): radix-tree KV block reuse with
LRU eviction over the paged serving stack.

Three layers of coverage:

- ``PrefixCache`` unit tests against a bare ``PagedKVCache``: matching,
  donation dedup, the eviction-order invariants (leaf-before-parent,
  refcount>1 never evicted, pinned never evicted, deterministic LRU
  tie-break), allocator reclaim wiring, and the ``prefix.donate`` /
  ``prefix.evict`` fault points leaving zero leaks.
- Server-level tests on the StubModel double (and one real llama):
  auto hits emit BIT-IDENTICAL tokens to cold-cache runs (greedy and
  seeded sampling), prefill savings are asserted via stats/telemetry
  counters (never wall-clock), registered prefixes pin donated pages,
  eviction keeps tiny pools serving, fault injection defers instead of
  failing.
- A chaos suite (``chaos`` marker): 30% fault rates on the prefix
  points during eviction storms — survivors bit-exact, pool balanced,
  same seed same trace.
"""
import numpy as np
import pytest

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_cache import OutOfPages, PagedKVCache
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.reliability import (CallbackError, CircuitBreaker,
                                    FaultInjector, InjectedFault,
                                    RetryPolicy, faults)
from paddle_tpu.telemetry import MetricRegistry, ServerTelemetry

PG = 4


def _cache(num_pages=17, injector=None):
    kv = PagedKVCache(num_pages=num_pages, page_size=PG, max_slots=4,
                      pages_per_slot=8)
    return PrefixCache(kv, fault_injector=injector), kv


def _donate(cache, kv, ids, extra_pages=0):
    """Simulate a finished slot: alloc the prompt's pages (+ budget),
    fill nothing (host-side tests), donate."""
    ids = np.asarray(ids, np.int32)
    pages = kv.alloc(-(-len(ids) // PG) + extra_pages)
    return cache.donate(ids, pages, len(ids))


def _ids(*toks):
    return np.asarray(toks, np.int32)


class TestRadixTree:
    def test_donate_then_lookup_longest_run(self):
        cache, kv = _cache()
        ids = np.arange(10, dtype=np.int32)          # 2 full pages + tail
        new = _donate(cache, kv, ids)
        assert new == 2 and cache.cached_pages == 2
        assert kv.used_pages() == 2                  # tail page released
        m = cache.lookup(ids, len(ids) - 1)
        assert m.tokens == 8 and len(m.pages) == 2
        # page-granular: an 8-token probe may use at most 1 page (the
        # remainder must keep >= 1 token for the prefill)
        m = cache.lookup(ids[:8], 7)
        assert m.tokens == 4
        # diverging second page -> only the first page matches
        other = np.concatenate([ids[:4], _ids(9, 9, 9, 9)])
        assert cache.lookup(other, 7).tokens == 4
        assert cache.lookup(_ids(5, 5, 5, 5), 3) is None

    def test_donate_dedup_releases_duplicates(self):
        cache, kv = _cache()
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        free0 = kv.free_pages()
        new = _donate(cache, kv, ids, extra_pages=3)  # replay + budget
        assert new == 0
        assert cache.dedup_pages_total == 2
        assert kv.free_pages() == free0               # all returned
        assert cache.cached_pages == 2

    def test_eviction_leaf_before_parent(self):
        cache, kv = _cache()
        ids = np.arange(12, dtype=np.int32)           # 3-node chain
        _donate(cache, kv, ids)
        assert cache.evict(1) == 1
        # the deepest page went first; the chain prefix still matches
        assert cache.lookup(ids, 11).tokens == 8
        assert cache.evict(1) == 1
        assert cache.lookup(ids, 11).tokens == 4
        assert kv.used_pages() == 1

    def test_shared_pages_never_evicted(self):
        cache, kv = _cache()
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        m = cache.lookup(ids, 8)                      # both pages
        kv.admit_slot(0, 12, shared_pages=m.pages)    # refcount -> 2
        assert cache.evictable_pages() == 0           # chain blocked
        assert cache.evict(10) == 0
        kv.free_slot(0)
        assert cache.evictable_pages() == 2
        assert cache.evict(10) == 2
        assert kv.used_pages() == 0
        # sharing only the chain HEAD still leaves the leaf evictable
        _donate(cache, kv, ids)
        head = cache.lookup(ids, 4)
        kv.admit_slot(0, 8, shared_pages=head.pages)
        assert cache.evictable_pages() == 1
        assert cache.evict(10) == 1                   # the leaf only
        kv.free_slot(0)

    def test_pinned_never_evicted_and_accounting(self):
        cache, kv = _cache()
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        run = cache.node_run(ids)
        cache.extend_pinned(ids, run, [])
        assert (cache.pinned_pages, cache.cached_pages) == (2, 0)
        assert cache.evict(10) == 0
        # an unpinned extension under the pinned chain still evicts
        ext = np.arange(16, dtype=np.int32)
        _donate(cache, kv, ext)
        assert cache.cached_pages == 2
        assert cache.evict(10) == 2
        assert cache.pinned_pages == 2 and kv.used_pages() == 2

    def test_lru_order_and_deterministic_tiebreak(self):
        cache, kv = _cache()
        a, b = _ids(1, 1, 1, 1), _ids(2, 2, 2, 2)
        _donate(cache, kv, a)
        _donate(cache, kv, b)                          # b more recent
        cache.use(cache.lookup(a, 5))                  # a now most recent
        assert cache.evict(1) == 1
        assert cache.lookup(b, 5) is None              # LRU: b went first
        assert cache.lookup(a, 5) is not None
        # tie-break: equal last_used falls back to insertion order
        c, d = _ids(3, 3, 3, 3), _ids(4, 4, 4, 4)
        _donate(cache, kv, c)
        _donate(cache, kv, d)
        for key, node in cache._root.children.items():
            node.last_used = 7
        evicted_first = min(cache._root.children.values(),
                            key=lambda n: n.seq)
        cache.evict(1)
        assert cache.lookup(
            np.asarray(evicted_first.key, np.int32), 5) is None

    def test_protect_shields_nodes_across_reclaim(self):
        cache, kv = _cache(num_pages=6)                # 5 usable
        ids = np.arange(8, dtype=np.int32)
        _donate(cache, kv, ids)
        run = cache.node_run(ids)
        cache.protect(run)
        assert cache.evictable_pages() == 0
        assert cache.evict(10) == 0
        cache.protect(())
        assert cache.evictable_pages() == 2

    def test_reclaimer_wired_into_alloc(self):
        cache, kv = _cache(num_pages=6)                # 5 usable
        kv.reclaimer = cache.evict
        _donate(cache, kv, np.arange(12, dtype=np.int32))
        assert kv.free_pages() == 2
        pages = kv.alloc(4)                            # forces 2 evictions
        assert len(pages) == 4
        assert cache.evicted_pages_total == 2
        kv.release(pages)
        with pytest.raises(OutOfPages):
            kv.alloc(6)                                # > usable, even evicting

    def test_donate_fault_leaves_tree_and_refcounts_untouched(self):
        fi = FaultInjector(seed=3).on(faults.PREFIX_DONATE, schedule=[0])
        cache, kv = _cache(injector=fi)
        ids = np.arange(8, dtype=np.int32)
        pages = kv.alloc(2)
        with pytest.raises(InjectedFault):
            cache.donate(ids, pages, len(ids))
        assert cache.cached_pages == 0 and cache.lookup(ids, 7) is None
        kv.release(pages)                              # caller's fallback
        assert kv.used_pages() == 0
        _donate(cache, kv, ids)                        # next visit clean
        assert cache.cached_pages == 2

    def test_evict_fault_aborts_sweep_cleanly(self):
        fi = FaultInjector(seed=3).on(faults.PREFIX_EVICT, schedule=[0])
        cache, kv = _cache(injector=fi)
        _donate(cache, kv, np.arange(8, dtype=np.int32))
        with pytest.raises(InjectedFault):
            cache.evict(1)
        assert cache.cached_pages == 2                 # nothing removed
        assert cache.evict(1) == 1                     # next sweep works

    def test_stats_snapshot(self):
        cache, kv = _cache()
        _donate(cache, kv, np.arange(8, dtype=np.int32))
        _donate(cache, kv, np.arange(8, dtype=np.int32))
        cache.evict(1)
        s = cache.stats()
        assert s["donated_pages_total"] == 2
        assert s["dedup_pages_total"] == 2
        assert s["evicted_pages_total"] == 1
        assert s["cached_pages"] == 1 and s["pinned_pages"] == 0


# ---------------------------------------------------------------- server


def _srv(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 32)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 4)
    return ContinuousBatchingServer(StubModel(), **kw)


def _usable(srv):
    return srv._kv.num_pages - 1


class TestAutoPrefixServer:
    def test_auto_hit_parity_and_counted_savings(self):
        """Acceptance: a prompt extending a previously-served prompt
        emits bit-identical tokens to a cold run, and the saved prefill
        work shows up in stats + telemetry counters."""
        tele = ServerTelemetry(registry=MetricRegistry())
        srv = _srv(telemetry=tele)
        a = np.arange(12, dtype=np.int32) % 16
        b = np.concatenate([a, _ids(3, 1)])
        ra = srv.submit(a, max_new_tokens=4)
        srv.run()
        rb = srv.submit(b, max_new_tokens=5)
        out = srv.run()[rb]
        np.testing.assert_array_equal(out, stub_tokens(b, 5))
        cold = _srv()
        rc = cold.submit(b, max_new_tokens=5)
        np.testing.assert_array_equal(cold.run()[rc], out)
        assert srv.stats["prefix_auto_hits"] == 1
        assert srv.stats["prefix_auto_hit_tokens"] == 12
        assert srv.stats["prefill_tokens"] == 12 + 2   # vs 12 + 14 cold
        assert cold.stats["prefill_tokens"] == 14
        reg = tele.registry
        pfx = reg.get("serving_prefix_cache_total")
        assert pfx.labels(result="auto_hit").value == 1.0
        assert pfx.labels(result="auto_miss").value == 1.0
        assert reg.get("kv_prefix_donated_pages_total").value == 3.0
        assert reg.get("kv_prefix_cached_pages").value == 3.0
        assert reg.get("kv_prefix_hit_tokens").value == 12.0
        tok = reg.get("serving_tokens_total")
        assert tok.labels(kind="prefill").value == 14.0
        assert tok.labels(kind="prefix_hit").value == 12.0

    def test_shared_system_prompt_workload_saves_prefill(self):
        """Acceptance: N requests sharing a system prompt measurably
        reduce prefill page writes vs auto_prefix_cache=False —
        asserted via counters, not wall-clock."""
        rng = np.random.default_rng(7)
        system = rng.integers(0, 16, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.integers(0, 16, (3,)).astype(np.int32)])
            for _ in range(6)]

        def run(auto):
            srv = _srv(max_slots=1, auto_prefix_cache=auto)
            outs = {}
            for p in prompts:
                rid = srv.submit(p, max_new_tokens=4)
                outs[rid] = srv.run()[rid]
            return srv, list(outs.values())

        on_srv, on_outs = run(True)
        off_srv, off_outs = run(False)
        for got, want, p in zip(on_outs, off_outs, prompts):
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got, stub_tokens(p, 4))
        # every request after the first hits the shared 8-token page run
        assert on_srv.stats["prefix_auto_hits"] == 5
        assert on_srv.stats["prefix_auto_hit_tokens"] == 5 * 8
        assert on_srv.stats["prefill_tokens"] == \
            off_srv.stats["prefill_tokens"] - 5 * 8
        assert off_srv.stats["prefix_auto_hits"] == 0
        assert off_srv.pool_balance() == (_usable(off_srv), 0, 0, 0)

    def test_sampled_auto_hit_parity_seeded(self):
        warm = _srv(do_sample=True, temperature=1.2, top_k=5, seed=0)
        cold = _srv(do_sample=True, temperature=1.2, top_k=5, seed=0)
        a = np.arange(8, dtype=np.int32)
        b = np.concatenate([a, _ids(2, 7, 1)])
        warm.submit(a, max_new_tokens=4, seed=11)
        warm.run()
        rw = warm.submit(b, max_new_tokens=6, seed=99)
        rc = cold.submit(b, max_new_tokens=6, seed=99)
        np.testing.assert_array_equal(warm.run()[rw], cold.run()[rc])
        assert warm.stats["prefix_auto_hits"] == 1

    def test_identical_prompt_replay_dedups_pages(self):
        srv = _srv()
        p = np.arange(12, dtype=np.int32) % 16
        for _ in range(3):
            rid = srv.submit(p, max_new_tokens=4)
            np.testing.assert_array_equal(srv.run()[rid],
                                          stub_tokens(p, 4))
        free, live, pinned, cached = srv.pool_balance()
        assert (live, pinned, cached) == (0, 0, 3)     # stored ONCE
        assert free == _usable(srv) - 3
        assert srv.stats["prefix_auto_hits"] == 2

    def test_eviction_keeps_tiny_pool_serving(self):
        rng = np.random.default_rng(0)
        srv = _srv(num_pages=9)                        # 8 usable pages
        seen_evictions = 0
        for _ in range(6):
            p = rng.integers(0, 16, (8,)).astype(np.int32)
            rid = srv.submit(p, max_new_tokens=4)      # extent 12 -> 3 pages
            np.testing.assert_array_equal(srv.run()[rid],
                                          stub_tokens(p, 4))
            free, live, pinned, cached = srv.pool_balance()
            assert live == 0
            assert free + pinned + cached == 8
        assert srv._prefix.evicted_pages_total > 0     # pressure hit LRU
        assert srv._prefix.cached_pages > 0            # cache survives

    def test_register_prefix_adopts_and_pins_donated_pages(self):
        srv = _srv()
        p = np.arange(8, dtype=np.int32)
        srv.submit(p, max_new_tokens=4)
        srv.run()
        assert srv.pool_balance() == (_usable(srv) - 2, 0, 0, 2)
        used0 = srv._kv.used_pages()
        assert srv.register_prefix(p) == 8
        # adopted, not re-allocated: same pages, now pinned
        assert srv._kv.used_pages() == used0
        assert srv.pool_balance() == (_usable(srv) - 2, 0, 2, 0)
        # pinned entries survive an eviction storm that empties the rest
        rng = np.random.default_rng(1)
        for _ in range(8):
            q = rng.integers(0, 16, (8,)).astype(np.int32)
            srv.submit(q, max_new_tokens=4)
            srv.run()
        assert srv.pool_balance()[2] == 2              # still pinned
        rid = srv.submit(np.concatenate([p, _ids(1, 2)]),
                         max_new_tokens=4)
        srv.run()
        assert srv.stats["prefix_hit_tokens"] >= 8     # registered hit

    def test_evict_fault_defers_admission_not_fails(self):
        fi = FaultInjector(seed=1).on(faults.PREFIX_EVICT, schedule=[0])
        srv = _srv(max_slots=1, num_pages=9, fault_injector=fi)
        rng = np.random.default_rng(2)
        a = rng.integers(0, 16, (12,)).astype(np.int32)
        srv.submit(a, max_new_tokens=4)
        srv.run()                                      # leaves 3 cached
        b = rng.integers(0, 16, (20,)).astype(np.int32)  # needs eviction
        rb = srv.submit(b, max_new_tokens=4)
        out = srv.run()
        np.testing.assert_array_equal(out[rb], stub_tokens(b, 4))
        assert fi.fired(faults.PREFIX_EVICT) == 1      # sweep 0 aborted
        assert rb not in srv.failures                  # deferred, not failed
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0 and free + cached == 8

    def test_donate_fault_frees_pages_instead_of_caching(self):
        fi = FaultInjector(seed=1).on(faults.PREFIX_DONATE,
                                      probability=1.0)
        srv = _srv(fault_injector=fi)
        p = np.arange(12, dtype=np.int32) % 16
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 4))
        assert srv.pool_balance() == (_usable(srv), 0, 0, 0)  # no leak
        assert fi.fired(faults.PREFIX_DONATE) == 1
        assert srv.stats["prefix_auto_hits"] == 0

    def test_auto_off_keeps_pr1_semantics(self):
        srv = _srv(auto_prefix_cache=False)
        p = np.arange(12, dtype=np.int32) % 16
        srv.submit(p, max_new_tokens=4)
        srv.run()
        assert srv.pool_balance() == (_usable(srv), 0, 0, 0)
        rid = srv.submit(np.concatenate([p, _ids(1)]), max_new_tokens=4)
        srv.run()
        assert srv.stats["prefix_auto_hits"] == 0
        assert srv.stats["prefix_hit_tokens"] == 0

    def test_chunked_prefill_pad_guard_trims_unsafe_match(self):
        """DENSE prefill mode: a tree hit whose remainder would
        chunk-pad past max_cache_len is trimmed (here: to nothing)
        instead of overflowing the cache rows — the submit-time bound
        only knew the hits registered THEN (ADVICE r5 #2 lineage)."""
        rng = np.random.default_rng(3)
        srv = _srv(max_slots=1, prefill_chunk=8, prefill_mode="dense")
        donor = rng.integers(0, 16, (12,)).astype(np.int32)
        srv.submit(donor, max_new_tokens=4)
        srv.run()
        # shares exactly one page with the donor; remainder 25 tokens
        # would pad to 32 rows -> 4 + 32 > 32 overflows, so no auto hit
        p = np.concatenate([donor[:4],
                            rng.integers(0, 16, (25,)).astype(np.int32)])
        rid = srv.submit(p, max_new_tokens=3)
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 3))
        assert srv.stats["prefix_auto_hits"] == 0

    def test_ragged_mode_never_pads_so_match_survives(self):
        """RAGGED prefill mode (ISSUE 6 satellite): the same workload
        KEEPS the hit — ragged remainders are chunked by the per-tick
        token budget at arbitrary cut points, never padded, so the
        chunk-pad trim (and the submit-time pad bound) do not apply."""
        rng = np.random.default_rng(3)
        srv = _srv(max_slots=1, prefill_chunk=8)     # ragged default
        assert srv.prefill_mode == "ragged"
        donor = rng.integers(0, 16, (12,)).astype(np.int32)
        srv.submit(donor, max_new_tokens=4)
        srv.run()
        p = np.concatenate([donor[:4],
                            rng.integers(0, 16, (25,)).astype(np.int32)])
        rid = srv.submit(p, max_new_tokens=3)        # 29 + 3 fits 32
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 3))
        assert srv.stats["prefix_auto_hits"] == 1
        assert srv.stats["prefix_auto_hit_tokens"] == 4

    def test_llama_auto_hit_matches_solo_generate(self):
        """Real-model acceptance: the auto hit's gather-seeded remainder
        prefill + page-shared decode is bit-identical to a solo
        generate()."""
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(21)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(4)
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged", page_size=8)
        donor = rng.integers(0, 256, (12,)).astype(np.int32)
        srv.submit(donor, max_new_tokens=4)
        srv.run()
        p = np.concatenate([donor[:8],
                            rng.integers(0, 256, (3,)).astype(np.int32)])
        rid = srv.submit(p, max_new_tokens=6)
        out = srv.run()[rid]
        want = model.generate(pt.to_tensor(p[None]), max_new_tokens=6,
                              max_cache_len=64).numpy()[0, len(p):]
        np.testing.assert_array_equal(out, want)
        assert srv.stats["prefix_auto_hits"] == 1
        assert srv.stats["prefix_auto_hit_tokens"] == 8


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
class TestEvictionChaos:
    def _injector(self, seed):
        return (FaultInjector(seed=seed)
                .on(faults.PREFILL, probability=0.15)
                .on(faults.DECODE_TICK, probability=0.1)
                .on(faults.PAGE_ALLOC, probability=0.1)
                .on(faults.PREFIX_EVICT, probability=0.3)
                .on(faults.PREFIX_DONATE, probability=0.3))

    def _srv(self, fi, **kw):
        kw.setdefault("max_slots", 2)
        kw.setdefault("max_cache_len", 32)
        kw.setdefault("cache_backend", "paged")
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 11)       # 10 usable: constant pressure
        kw.setdefault("retry_policy", RetryPolicy(base_delay_s=0.0,
                                                  jitter=0.0))
        kw.setdefault("breaker", CircuitBreaker(failure_threshold=10_000))
        return ContinuousBatchingServer(StubModel(), fault_injector=fi,
                                        **kw)

    def _drive(self, srv, max_ticks=5000):
        ticks = 0
        while True:
            with srv._lock:
                busy = srv._busy_locked()   # incl. mid-prefill slots
            if not busy:
                return
            try:
                srv.step()
            except CallbackError:
                pass
            except Exception:
                pass                         # transient tick fault: retry
            ticks += 1
            assert ticks < max_ticks, "chaos drive did not converge"

    def _workload(self, seed=5):
        rng = np.random.default_rng(seed)
        system = rng.integers(0, 16, (8,)).astype(np.int32)
        return [np.concatenate(
            [system, rng.integers(0, 16, (int(n),)).astype(np.int32)])
            for n in rng.integers(1, 6, (16,))]

    def test_eviction_storm_zero_leaks(self):
        """Acceptance: 30% fault rate on prefix.evict/donate during an
        eviction storm — survivors bit-exact, pool_balance reports zero
        leaked pages."""
        fi = self._injector(seed=606)
        srv = self._srv(fi)
        prompts = self._workload()
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        self._drive(srv)
        outs = srv._results
        served = 0
        for rid, p in zip(rids, prompts):
            if rid in outs:
                served += 1
                np.testing.assert_array_equal(outs[rid],
                                              stub_tokens(p, 4))
        assert served > 0
        assert fi.fired(faults.PREFIX_EVICT) \
            + fi.fired(faults.PREFIX_DONATE) > 0, "prefix chaos idle"
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0, f"leaked {live} pages"
        assert free + pinned + cached == srv._kv.num_pages - 1

    def test_eviction_storm_with_pinned_prefix(self):
        """Pinned pages survive the storm; donated pages churn around
        them; books stay balanced."""
        fi = self._injector(seed=77)
        fi.disarm()
        srv = self._srv(fi)
        system = self._workload()[0][:8]
        srv.register_prefix(system)
        fi.arm()
        for p in self._workload(seed=9):
            srv.submit(p, max_new_tokens=3)
        self._drive(srv)
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0 and pinned == 2
        assert free + pinned + cached == srv._kv.num_pages - 1

    def test_same_seed_identical_trace_and_cache_state(self):
        def run_once():
            fi = self._injector(seed=4242)
            srv = self._srv(fi)
            for p in self._workload(seed=11):
                srv.submit(p, max_new_tokens=4)
            self._drive(srv)
            results = {r: tuple(int(x) for x in v)
                       for r, v in srv._results.items()}
            fails = {r: type(e).__name__
                     for r, e in srv.failures.items()}
            return (fi.trace, results, fails, srv.pool_balance(),
                    srv._prefix.stats())

        a, b = run_once(), run_once()
        assert a == b
        assert a[0], "deterministic run injected nothing"


# ------------------------------------------------- shared-prompt workload


class TestPrefixCacheBenchGuard:
    def test_shared_prompt_hit_rate_and_savings(self):
        """Counter-based guard: the shared-system-prompt workload must
        hit on every follow-up request and cut prefill tokens by the
        shared page run."""
        rng = np.random.default_rng(0)
        system = rng.integers(0, 16, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.integers(0, 16, (4,)).astype(np.int32)])
            for _ in range(8)]
        srv = _srv(max_slots=1, max_cache_len=64, page_size=4)
        for p in prompts:
            rid = srv.submit(p, max_new_tokens=8)
            np.testing.assert_array_equal(srv.run()[rid],
                                          stub_tokens(p, 8))
        hits = srv.stats["prefix_auto_hits"]
        assert hits == len(prompts) - 1
        assert srv.stats["prefix_auto_hit_tokens"] == hits * 16
