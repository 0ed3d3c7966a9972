"""Sharded paged serving (ISSUE 16): the K/V page pool spans a
tensor-parallel mesh.

The pool shards on the kv-head dimension over the mesh's ``mp`` axis;
block tables, per-slot lengths and ALL host-side bookkeeping
(allocator, grow/preempt/donate, radix tree, refcounts) stay global.
Contracts pinned here:

- bit-exact token parity (greedy AND seeded-sampled) vs the
  single-device oracle, including an optimistic-admission
  preemption/replay under pool pressure;
- per-device pool page bytes shrink to ~1/mp with block tables
  replicated;
- ``pool_balance()`` / ``occupancy()`` report balanced per-shard views
  and the kill-drill postmortem freezes them;
- steady-state sharded decode is zero-recompile after warmup, and a
  CostCatalog SHARED across servers at different mp never trips the
  post-warmup recompile alarm (ops are namespaced ``decode_mp4``);
- the shard_map'd Pallas kernels (interpret mode) match the unsharded
  launches bit-for-bit.

Runs under conftest's forced 8 host devices; skips cleanly elsewhere.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as pt
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import ragged_prefill as rp

pytestmark = [
    pytest.mark.mesh,
    pytest.mark.skipif(
        len(jax.devices()) < 4,
        reason="needs >= 4 forced host devices "
               "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"),
]


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("mp",))


@pytest.fixture(scope="module")
def model4():
    """llama with 4 kv heads — divisible by mp=2 AND mp=4 (llama_tiny
    has 2, which caps it at mp=2)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=1,
                      num_heads=8, num_kv_heads=4,
                      intermediate_size=128, max_seq_len=128)
    pt.seed(21)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, seed=7, lo=3, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (int(k),)).astype(np.int32)
            for k in rng.integers(lo, hi, (n,))]


def _run_pair(model, mesh, prompts, n_new, seeds=None, srv_kw=None):
    """The same workload through a single-device oracle and a mesh
    server (identical config otherwise); returns (oracle, sharded)
    servers after asserting bit-identical per-request tokens."""
    kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
              page_size=8, num_pages=24)
    kw.update(srv_kw or {})
    oracle = ContinuousBatchingServer(model, **kw)
    sharded = ContinuousBatchingServer(model, mesh=mesh, **kw)
    seeds = seeds or [None] * len(prompts)
    ra = [oracle.submit(p, max_new_tokens=n_new, seed=s)
          for p, s in zip(prompts, seeds)]
    rb = [sharded.submit(p, max_new_tokens=n_new, seed=s)
          for p, s in zip(prompts, seeds)]
    oa, ob = oracle.run(), sharded.run()
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(oa[a], ob[b])
    return oracle, sharded


class TestShardedPagedParity:
    @pytest.mark.slow
    def test_greedy_parity_preemption_and_pool_shrink_mp4(self, model4):
        """The acceptance drill: optimistic admission on a tight pool
        forces a preemption/replay on BOTH servers; tokens stay
        bit-exact, the mesh pool's per-device bytes measure ~1/4 of the
        oracle's, block tables stay replicated, and the kill-drill
        postmortem freezes balanced per-shard views."""
        prompts = _prompts(3, seed=11, lo=7, hi=10)
        oracle, sharded = _run_pair(
            model4, _mesh(4), prompts, n_new=24,
            srv_kw=dict(num_pages=8, admission="optimistic",
                        headroom_pages=1, recorder=True))
        # pressure really happened, identically on both sides
        bal = sharded.pool_balance()
        assert bal.preemptions >= 1
        assert bal.preemptions == oracle.pool_balance().preemptions
        # per-device pool bytes: shard0 holds <= (1/4 + eps) of the
        # oracle's pool (kv-head dim split 4 ways)
        for name in ("k", "v"):
            whole = oracle._caches["pool"][name]
            part = sharded._caches["pool"][name]
            assert part.nbytes == whole.nbytes            # global shape
            shard0 = part.addressable_shards[0].data.nbytes
            assert shard0 <= whole.nbytes // 4 + 128
        assert sharded._caches["bt"].sharding.is_fully_replicated
        # per-shard balance views: structural balance made explicit
        assert bal.num_shards == 4
        assert len(bal.per_shard) == 4
        assert all(s == bal.per_shard[0] for s in bal.per_shard)
        assert bal.per_shard[0]["free"] == bal[0]
        assert bal.shard_page_bytes is not None
        occ = sharded._kv.occupancy(num_shards=4)
        assert [s["used_pages"] for s in occ["shards"]] \
            == [occ["used_pages"]] * 4
        # kill drill: the postmortem bundle freezes the shard views
        sharded.kill()
        pm = sharded.postmortems()[-1]
        sec = pm["pool_balance"]
        assert sec["num_shards"] == 4
        assert len(sec["per_shard"]) == 4
        assert sec["shard_page_bytes"] == bal.shard_page_bytes
        assert len(pm["block_table"]["shards"]) == 4

    def test_seeded_sampled_parity_mp4(self, model4):
        prompts = _prompts(2, seed=12)
        _run_pair(model4, _mesh(4), prompts, n_new=8,
                  seeds=[101, 102],
                  srv_kw=dict(do_sample=True, temperature=0.8,
                              top_k=20, top_p=0.9))

    @pytest.mark.slow
    def test_greedy_parity_mp2_llama_tiny(self):
        """llama_tiny's 2 kv heads divide a 2-way mesh — the stock tiny
        config serves sharded without a custom head count. (slow:
        compile-heavy secondary coverage — tier-1 carries the mp=4
        acceptance drill on the same builder.)"""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(22)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        _run_pair(model, _mesh(2), _prompts(1, seed=13), n_new=5)

    @pytest.mark.slow
    def test_greedy_parity_mp2_mixtral_and_gpt(self):
        """The other two paged bundle builders take the mesh too:
        mixtral (GQA + expert-parallel MoE) and gpt (MHA, fused qkv).
        (slow: two extra model families' compiles; the sharding path
        they exercise is the same `_mesh_paged_caches` placement the
        tier-1 llama drill pins.)"""
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        for seed, build in ((23, lambda: MixtralForCausalLM(
                                 mixtral_tiny())),
                            (24, lambda: GPTForCausalLM(gpt2_tiny()))):
            pt.seed(seed)
            model = build()
            model.eval()
            _run_pair(model, _mesh(2), _prompts(1, seed=seed), n_new=4)

    def test_indivisible_kv_heads_fall_back_to_replicated(self):
        """llama_tiny kv heads (2) aren't divisible by 4: the pool
        falls back to replicated placement (same rule as _apply_mesh
        weights) and still serves bit-exactly."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(25)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        _, sharded = _run_pair(model, _mesh(4), _prompts(1, seed=14),
                               n_new=3)
        assert sharded._pool_shards == 1
        assert sharded._caches["pool"]["k"].sharding.is_fully_replicated
        assert sharded.pool_balance().num_shards == 1

    def test_register_prefix_and_auto_cache_on_mesh(self, model4):
        """Prefix caching needs no mesh branch: cached page ids address
        the SHARDED pool (their K/V split across shards like any live
        page) while the radix tree, refcounts and pins stay host-side
        and global. A registered prefix pins pages, hits stay
        bit-exact vs the oracle, and a repeated prompt auto-hits off
        donated pages — on the mesh exactly as on one device."""
        rng = np.random.default_rng(19)
        prefix = rng.integers(0, 256, (10,)).astype(np.int32)
        tails = [rng.integers(0, 256, (n,)).astype(np.int32)
                 for n in (3, 5)]
        prompts = [np.concatenate([prefix, t]) for t in tails]
        # same tail resubmitted: the second pass auto-hits donations
        prompts = prompts + [prompts[0]]
        kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                  page_size=8, num_pages=24)
        oracle = ContinuousBatchingServer(model4, **kw)
        sharded = ContinuousBatchingServer(model4, mesh=_mesh(4), **kw)
        for srv in (oracle, sharded):
            assert srv.register_prefix(prefix) == 10
        bal = sharded.pool_balance()
        assert bal[2] == 1                      # one pinned page
        assert bal.per_shard[0]["pinned"] == 1  # on every shard
        ra = [oracle.submit(p, max_new_tokens=4) for p in prompts]
        rb = [sharded.submit(p, max_new_tokens=4) for p in prompts]
        oa, ob = oracle.run(), sharded.run()
        for a, b in zip(ra, rb):
            np.testing.assert_array_equal(oa[a], ob[b])
        assert sharded.stats["prefix_auto_hits"] \
            == oracle.stats["prefix_auto_hits"]


class TestShardedCosts:
    def test_steady_state_sharded_decode_zero_recompile(self, model4):
        """Slot churn on the mesh after warmup must not recompile: the
        decode program's signature is static (pool + full slot batch),
        so wave 2's different prompts/slot refills reuse wave 1's
        executable — compile counts frozen, recompiles == 0."""
        srv = ContinuousBatchingServer(
            model4, max_slots=2, max_cache_len=64,
            cache_backend="paged", page_size=8, num_pages=24,
            mesh=_mesh(4), costs=True)
        wave1 = _prompts(3, seed=15, lo=5, hi=6)
        for p in wave1:
            srv.submit(p, max_new_tokens=8)
        srv.run()
        frozen = srv.costs.compiles()
        assert frozen.get("decode_mp4", 0) == 1   # namespaced, priced
        assert "decode" not in frozen             # bare name = mp1 only
        wave2 = _prompts(3, seed=16, lo=5, hi=6)  # same widths, new ids
        for p in wave2:
            srv.submit(p, max_new_tokens=8)
        srv.run()
        assert srv.costs.compiles() == frozen
        assert srv.costs.recompiles == 0

    def test_shared_catalog_across_mp_never_trips_alarm(self, model4):
        """One CostCatalog fronting an mp=1 and an mp=4 server (a fleet
        sharing a registry): the sharded server's ops are namespaced
        (``decode_mp4``), so the warmed mp=1 ``decode`` op never sees a
        new shape signature — mesh size is a deployment choice, not a
        recompile."""
        from paddle_tpu.telemetry import CostCatalog
        cat = CostCatalog(warm_after_ticks=1)
        kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                  page_size=8, num_pages=24, costs=cat)
        flat = ContinuousBatchingServer(model4, **kw)
        for p in _prompts(2, seed=17):
            flat.submit(p, max_new_tokens=8)
        flat.run()
        assert cat.warmed_op("decode")
        sharded = ContinuousBatchingServer(model4, mesh=_mesh(4), **kw)
        for p in _prompts(2, seed=18):
            sharded.submit(p, max_new_tokens=8)
        sharded.run()
        comp = cat.compiles()
        assert comp.get("decode") == 1 and comp.get("decode_mp4") == 1
        assert cat.recompiles == 0


class TestShardedKernels:
    """shard_map'd Pallas launches (interpret mode) vs the unsharded
    kernel: per-kv-head-shard splits must be bit-exact restitches."""

    def _pool(self, S, kvh, hd, P, pg, maxp, seed):
        rng = np.random.RandomState(seed)
        r = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) * .5)
        kp, vp = r(P, pg, kvh, hd), r(P, pg, kvh, hd)
        bt = jnp.asarray(np.stack([
            rng.choice(np.arange(1, P), maxp, replace=False)
            for _ in range(S)]).astype(np.int32))
        return r, kp, vp, bt

    def test_paged_decode_shard_map_matches_unsharded(self):
        S, nh, kvh, hd, P, pg, maxp = 4, 8, 4, 32, 12, 8, 4
        r, kp, vp, bt = self._pool(S, kvh, hd, P, pg, maxp, seed=31)
        q = r(S, nh, hd)
        lengths = jnp.asarray(np.array([pg, 13, 1, maxp * pg], np.int32))
        want = pa.paged_attention(q, kp, vp, bt, lengths, interpret=True)
        got = pa.paged_attention(q, kp, vp, bt, lengths, interpret=True,
                                 mesh=_mesh(4))
        # per-shard launches batch 1 kv head where the unsharded kernel
        # batches 4 — CPU interpret mode vectorizes the reductions in a
        # different order, so parity is to float32 ulp, not bitwise
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   rtol=1e-6, atol=1e-7)

    def test_ragged_prefill_shard_map_matches_unsharded(self):
        S, C, nh, kvh, hd, P, pg, maxp = 3, 8, 8, 4, 32, 12, 8, 4
        r, kp, vp, bt = self._pool(S, kvh, hd, P, pg, maxp, seed=32)
        q = r(S, C, nh, hd)
        t0 = jnp.asarray(np.array([0, 5, 16], np.int32))
        take = jnp.asarray(np.array([8, 5, 0], np.int32))   # idle slot
        want = rp.ragged_prefill_attention(q, kp, vp, bt, t0, take,
                                           interpret=True)
        got = rp.ragged_prefill_attention(q, kp, vp, bt, t0, take,
                                          interpret=True, mesh=_mesh(4))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_kv_head_shards_divisibility_rule(self):
        mesh = _mesh(4)
        assert pa.kv_head_shards(mesh, 4, 8) == 4
        assert pa.kv_head_shards(mesh, 2, 4) == 1     # kvh % mp != 0
        assert pa.kv_head_shards(None, 4, 8) == 1
        assert pa.kv_head_shards(_mesh(2), 2, 4) == 2


class TestPerShardAccounting:
    def test_occupancy_shards_view_is_host_side_only(self):
        """occupancy(num_shards=N) is pure host bookkeeping — no mesh
        required — and every shard reports the global counts (the pool
        splits on kv-heads, so each page id lives on every shard)."""
        kv = PagedKVCache(num_pages=9, page_size=8, max_slots=2,
                          pages_per_slot=4)
        kv.admit_slot(0, 10)
        kv.admit_slot(1, 5)
        occ = kv.occupancy(num_shards=4)
        assert len(occ["shards"]) == 4
        for i, s in enumerate(occ["shards"]):
            assert s == {"shard": i, "free_pages": occ["free_pages"],
                         "used_pages": occ["used_pages"]}
        assert "shards" not in kv.occupancy()      # default: unchanged
