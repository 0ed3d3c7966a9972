"""Batched chunked ragged prefill inside the decode tick (ISSUE 6).

Four layers of coverage:

- Kernel: the Pallas ragged-prefill kernel (interpret mode) must match
  the gather reference on packed variable-length segments with prefix
  offsets, skip idle slots, and never read positions beyond a row's
  causal frontier.
- Generation: the paged bundle's ragged-prefill entry point writes
  cache rows and emits last-row logits BIT-IDENTICAL to the dense
  batch-1 prefill — packed multi-slot launches and chunk-straddling
  resumes at t0 > 0 included.
- Server: ``prefill_mode="ragged"`` (the paged default) emits
  bit-identical tokens to the dense backend AND the dense-prefill paged
  baseline (greedy + seeded sampling, mixed lengths of 1 /
  page_size - 1 / page_size / multi-page / chunk-straddling, cold and
  auto-hit), with auto-hits counter-asserted to skip the
  page-gather→dense→scatter detour (``_seed_from_pages`` never runs,
  dispatches-per-admission drop vs the dense baseline).
- Scheduler: the per-tick token budget interleaves long prefills with
  decode (in-flight slots advance EVERY tick — the tick-budget
  starvation invariant), the T-1 cap keeps full-prefix hits serving,
  and mid-prefill slots tear down leak-free on cancel/deadline.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.models.generation import pool_heads
from paddle_tpu.ops.pallas import ragged_prefill as rp


def _rand(*shape, seed=0, scale=0.5):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


import functools


@functools.lru_cache(maxsize=1)
def _model():
    # one llama across the module: every parity test uses the same
    # (max_cache_len, page_size) bundles, so sharing the instance
    # shares the compiles through the model's bundle LRU — the suite
    # stays inside the tier-1 wall-clock budget
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(21)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _solo(model, ids, n_new, **kw):
    out = model.generate(pt.to_tensor(ids[None]), max_new_tokens=n_new,
                         max_cache_len=64, **kw).numpy()[0]
    return out[len(ids):]


# ------------------------------------------------------------- kernel


class TestRaggedPrefillKernel:
    @pytest.mark.parametrize("kvh,nh", [(2, 2), (2, 4)])  # MHA and GQA
    def test_kernel_matches_gather_oracle(self, kvh, nh):
        S, C, hd, P, pg, maxp = 3, 4, 32, 12, 8, 4
        q = _rand(S, C, nh, hd, seed=1)
        kp = _rand(P, pg, kvh, hd, seed=2)
        vp = _rand(P, pg, kvh, hd, seed=3)
        rng = np.random.RandomState(4)
        bt = jnp.asarray(np.stack([
            rng.choice(np.arange(1, P), maxp, replace=False)
            for _ in range(S)]).astype(np.int32))
        # prefix offsets: cold, mid-page resume, page-boundary resume
        t0 = jnp.asarray(np.array([0, 5, pg], np.int32))
        takes = np.array([C, 2, 3], np.int32)
        out = rp._ragged_prefill_pallas(q, kp, vp, bt, t0,
                                        jnp.asarray(takes), 0.2,
                                        interpret=True)
        ref = rp._ref_ragged_prefill(q, kp, vp, bt, t0, 0.2)
        for s in range(S):                  # live rows only
            np.testing.assert_allclose(
                np.asarray(out)[s, :takes[s]],
                np.asarray(ref)[s, :takes[s]], rtol=2e-5, atol=2e-5)

    def test_kernel_skips_idle_slots_and_masks_future(self):
        """An idle slot (take = 0) produces no NaN/Inf, and poisoning
        pool rows beyond every row's causal frontier must not change a
        single output bit."""
        S, C, nh, kvh, hd, P, pg, maxp = 2, 4, 2, 2, 16, 8, 4, 4
        q = _rand(S, C, nh, hd, seed=5)
        kp = _rand(P, pg, kvh, hd, seed=6)
        vp = _rand(P, pg, kvh, hd, seed=7)
        bt = jnp.asarray(np.array([[1, 2, 0, 0], [3, 4, 5, 0]],
                                  np.int32))
        t0 = jnp.asarray(np.array([2, 64], np.int32))
        take = jnp.asarray(np.array([4, 0], np.int32))
        out1 = rp.ragged_prefill_attention(q, kp, vp, bt, t0, take, 0.3,
                                           interpret=True)
        assert np.isfinite(np.asarray(out1)).all()
        # slot 0's last visible position is t0+C-1 = 5 (page 1, row 1):
        # poison everything after it
        kp2 = kp.at[2, 2:].set(1e3).at[5:].set(-1e3)
        vp2 = vp.at[2, 2:].set(1e3).at[5:].set(-1e3)
        out2 = rp.ragged_prefill_attention(q, kp2, vp2, bt, t0, take, 0.3,
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(out1)[0],
                                      np.asarray(out2)[0])

    def test_wide_chunk_tiles_query_rows(self):
        """Chunks wider than QUERY_TILE are cut into query tiles, each
        a run of grid steps at its own offset (bounded VMEM scratch on
        real TPUs — review finding); the tiled composition must match
        the untiled reference, including a slot whose live rows end
        mid-tile and an idle slot."""
        S, C, nh, kvh, hd, P, pg, maxp = 2, 16, 4, 2, 16, 16, 8, 8
        assert C > rp.QUERY_TILE
        q = _rand(S, C, nh, hd, seed=11)
        kp = _rand(P, pg, kvh, hd, seed=12)
        vp = _rand(P, pg, kvh, hd, seed=13)
        bt = jnp.asarray(np.array([[1, 2, 3, 4, 0, 0, 0, 0],
                                   [5, 6, 7, 8, 9, 0, 0, 0]], np.int32))
        t0 = jnp.asarray(np.array([3, 64], np.int32))
        take = jnp.asarray(np.array([10, 0], np.int32))
        out = rp.ragged_prefill_attention(q, kp, vp, bt, t0, take=take,
                                          sm_scale=0.25, interpret=True)
        ref = rp._ref_ragged_prefill(q, kp, vp, bt, t0, 0.25)
        np.testing.assert_allclose(np.asarray(out)[0, :10],
                                   np.asarray(ref)[0, :10],
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(out)).all()

    def test_ref_path_bitwise_matches_dense_prefill_attend(self):
        """The gather fallback mirrors generation._cached_attend op for
        op at prefill shapes — paging must not change a single bit."""
        from paddle_tpu.models.generation import _cached_attend
        S, C, nh, kvh, hd, T, pg = 2, 5, 4, 2, 16, 32, 8
        maxp = T // pg
        q = _rand(S, C, nh, hd, seed=8)
        kc = _rand(S, T, kvh, hd, seed=9)
        vc = _rand(S, T, kvh, hd, seed=10)
        t0 = jnp.asarray(np.array([3, 11], np.int32))
        kk = jnp.repeat(kc, nh // kvh, axis=2)
        vv = jnp.repeat(vc, nh // kvh, axis=2)
        want = _cached_attend(q, kk, vv, t0, C, 0.25)

        P = 1 + S * maxp
        kp = jnp.zeros((P, pg, kvh, hd), jnp.float32)
        vp = jnp.zeros((P, pg, kvh, hd), jnp.float32)
        bt = np.zeros((S, maxp), np.int32)
        for b in range(S):
            ids = 1 + b * maxp + np.arange(maxp)
            bt[b] = ids
            kp = kp.at[ids].set(kc[b].reshape(maxp, pg, kvh, hd))
            vp = vp.at[ids].set(vc[b].reshape(maxp, pg, kvh, hd))
        got = rp._ref_ragged_prefill(q, kp, vp, jnp.asarray(bt), t0,
                                     0.25)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- generation layer


class TestRaggedPrefillBundle:
    def test_packed_launch_bitwise_matches_dense_prefill(self):
        """Two slots' prompts in ONE ragged launch: pool rows and
        last-token logits bit-match each prompt's dense batch-1
        prefill; a chunk-straddling two-launch resume at t0 > 0
        bit-matches too."""
        m = _model()
        MCL, PG, NP, S = 64, 8, 33, 2
        dense = m._decode_bundle(MCL)
        paged = m._decode_bundle(MCL, cache_backend="paged",
                                 page_size=PG, num_pages=NP)
        assert len(paged) == 6          # ragged entry is element 5
        init_p, ragged_jit = paged[0], paged[5]
        rng = np.random.default_rng(0)
        ids_a = rng.integers(0, 256, (12,)).astype(np.int32)
        ids_b = rng.integers(0, 256, (7,)).astype(np.int32)
        lg_a, cd_a = m._run_prefill(dense, ids_a[None])
        lg_b, cd_b = m._run_prefill(dense, ids_b[None])

        caches = init_p(S)
        bt = np.zeros((S, MCL // PG), np.int32)
        bt[0, :2] = [1, 2]
        bt[1, :1] = [3]
        caches = dict(caches, bt=jnp.asarray(bt))
        C = 16
        rows = jnp.arange(S, dtype=jnp.int32)       # row j is slot j
        toks = np.zeros((S, C), np.int32)
        toks[0, :12] = ids_a
        toks[1, :7] = ids_b
        logits, caches = ragged_jit(
            jnp.asarray(toks), jnp.asarray(np.zeros((S,), np.int32)),
            caches, jnp.asarray(np.array([11, 6], np.int32)),
            jnp.asarray(np.array([12, 7], np.int32)), rows)
        np.testing.assert_array_equal(np.asarray(logits[0:1]),
                                      np.asarray(lg_a))
        np.testing.assert_array_equal(np.asarray(logits[1:2]),
                                      np.asarray(lg_b))
        pool_k = pool_heads(np.asarray(caches["pool"]["k"]),
                            m.cfg.num_kv_heads)
        ka = pool_k[:, [1, 2]].reshape(pool_k.shape[0], 16,
                                       *pool_k.shape[3:])[:, :12]
        np.testing.assert_array_equal(ka, np.asarray(cd_a["k"])[:, 0, :12])

        # chunk-straddling: 8 rows, then 4 more resumed at t0=8
        caches2 = init_p(S)
        bt2 = np.zeros((S, MCL // PG), np.int32)
        bt2[0, :2] = [4, 5]
        caches2 = dict(caches2, bt=jnp.asarray(bt2))
        c1 = np.zeros((S, 8), np.int32)
        c1[0, :8] = ids_a[:8]
        _, caches2 = ragged_jit(
            jnp.asarray(c1), jnp.asarray(np.array([0, MCL], np.int32)),
            caches2, jnp.asarray(np.zeros((S,), np.int32)),
            jnp.asarray(np.array([8, 0], np.int32)), rows)
        c2 = np.zeros((S, 8), np.int32)
        c2[0, :4] = ids_a[8:12]
        lg2, caches2 = ragged_jit(
            jnp.asarray(c2), jnp.asarray(np.array([8, MCL], np.int32)),
            caches2, jnp.asarray(np.array([3, 0], np.int32)),
            jnp.asarray(np.array([4, 0], np.int32)), rows)
        np.testing.assert_array_equal(np.asarray(lg2[0:1]),
                                      np.asarray(lg_a))
        pool_k2 = pool_heads(np.asarray(caches2["pool"]["k"]),
                             m.cfg.num_kv_heads)
        ka2 = pool_k2[:, [4, 5]].reshape(pool_k2.shape[0], 16,
                                         *pool_k2.shape[3:])[:, :12]
        np.testing.assert_array_equal(ka2,
                                      np.asarray(cd_a["k"])[:, 0, :12])


# -------------------------------------------------------- server parity


class TestRaggedServerParity:
    def _three_way(self, model, prompts, n_new, budget=None, **kw):
        """dense backend vs paged+dense prefill vs paged+ragged prefill:
        all three must emit bit-identical per-request tokens. Returns
        the ragged server."""
        seeds = list(range(100, 100 + len(prompts)))
        outs = []
        servers = []
        for mode_kw in ({"cache_backend": "dense"},
                        {"cache_backend": "paged", "page_size": 8,
                         "prefill_mode": "dense"},
                        {"cache_backend": "paged", "page_size": 8,
                         "prefill_mode": "ragged",
                         "prefill_tokens_per_tick": budget}):
            srv = ContinuousBatchingServer(model, max_slots=2,
                                           max_cache_len=64,
                                           **mode_kw, **kw)
            rids = [srv.submit(p, max_new_tokens=n_new, seed=s)
                    for p, s in zip(prompts, seeds)]
            res = srv.run()
            outs.append([res[r] for r in rids])
            servers.append(srv)
        for got_dense_paged, got_ragged, got_dense in zip(
                outs[1], outs[2], outs[0]):
            np.testing.assert_array_equal(got_dense_paged, got_dense)
            np.testing.assert_array_equal(got_ragged, got_dense)
        return servers[2]

    def test_greedy_parity_mixed_lengths(self):
        """Mixed prompt lengths: 1, page_size-1, page_size, multi-page
        — 5 requests through 2 slots (refill mid-run), all three
        prefill paths bit-identical."""
        model = _model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (1, 7, 8, 12, 17)]
        srv = self._three_way(model, prompts, 6)
        assert srv.prefill_mode == "ragged"
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0

    def test_greedy_parity_chunk_straddling_budget(self):
        """A 4-token-per-tick budget slices every prompt across ticks
        at arbitrary (non-page-aligned) cut points; tokens must not
        move a bit."""
        model = _model()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (9, 13, 5)]
        self._three_way(model, prompts, 5, budget=4)

    def test_sampled_parity_seeded(self):
        model = _model()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 11, 6)]
        self._three_way(model, prompts, 6, do_sample=True,
                        temperature=1.3, top_k=9)

    def test_auto_hit_parity_and_no_seed_detour(self):
        """Acceptance (ISSUE 6): an auto-hit admission in ragged mode
        NEVER calls _seed_from_pages (the page-gather→dense→scatter
        detour) — enforced by poisoning it — and still emits tokens
        bit-identical to a cold run and to solo generate."""
        model = _model()
        rng = np.random.default_rng(4)
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged", page_size=8)

        def _poisoned(pages):
            raise AssertionError("ragged auto-hit took the dense-seed "
                                 "detour")

        srv._seed_from_pages = _poisoned
        donor = rng.integers(0, 256, (12,)).astype(np.int32)
        srv.submit(donor, max_new_tokens=4)
        srv.run()
        p = np.concatenate([donor[:8],
                            rng.integers(0, 256, (3,)).astype(np.int32)])
        rid = srv.submit(p, max_new_tokens=6)
        out = srv.run()[rid]
        np.testing.assert_array_equal(out, _solo(model, p, 6))
        assert srv.stats["prefix_auto_hits"] == 1
        assert srv.stats["prefix_auto_hit_tokens"] == 8

    def test_dispatches_per_admission_drop_vs_dense_baseline(self):
        """Acceptance (ISSUE 6): counter-asserted dispatch reduction on
        the shared-prompt auto-hit workload — the PR-5 dense path pays
        seed-gather + per-request prefill + scatter + 3 state pushes
        per admission; ragged amortizes one launch + 3 batched pushes
        per tick."""
        rng = np.random.default_rng(7)
        system = rng.integers(0, 16, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.integers(0, 16, (3,)).astype(np.int32)])
            for _ in range(6)]

        def run(mode):
            srv = ContinuousBatchingServer(
                StubModel(), max_slots=1, max_cache_len=32,
                cache_backend="paged", page_size=4, prefill_mode=mode)
            for p in prompts:
                rid = srv.submit(p, max_new_tokens=4)
                np.testing.assert_array_equal(srv.run()[rid],
                                              stub_tokens(p, 4))
            assert srv.stats["admissions"] == len(prompts)
            return srv.stats["prefill_dispatches"] / len(prompts)

        dense_rate, ragged_rate = run("dense"), run("ragged")
        assert ragged_rate < dense_rate, \
            f"ragged {ragged_rate} !< dense {dense_rate}"


# ------------------------------------------------------------ scheduler


def _stub_srv(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 32)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 4)
    return ContinuousBatchingServer(StubModel(), **kw)


@pytest.mark.parametrize("tick", ["steady", "admission"])
def test_split_tick_dispatch_profile(tick):
    """What the paged tick dispatches, counted (ROADMAP A2 prices each):
    a steady decode tick is ONE program; a tick that admits adds one
    prefill launch, the block-table sync and the three batched slot-state
    pushes (token, position, key), whether the other slot is empty or
    decoding."""
    from paddle_tpu.telemetry import FlightRecorder
    rec = FlightRecorder()
    srv = _stub_srv(recorder=rec)
    srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=8)
    srv.step()
    srv.step()
    srv.step()
    srv.submit(np.asarray([1, 2, 3, 4, 5], np.int32), max_new_tokens=3)
    srv.step()
    prof = [e["dispatches"] for e in rec.events(kind="tick")]
    assert len(prof) == 4
    if tick == "steady":
        assert prof[1] == prof[2] == {"decode": 1}
    else:
        assert prof[0] == prof[3] == {"prefill": 1, "block_table": 1,
                                      "state_push": 3, "decode": 1}
    srv.run()


class TestInterleavedScheduler:
    def test_tick_budget_never_starves_inflight_decode(self):
        """Starvation invariant: while a long prompt streams in under a
        small per-tick budget, an already-decoding slot advances by
        tick_block tokens EVERY tick."""
        srv = _stub_srv(max_slots=2, max_cache_len=32,
                        prefill_tokens_per_tick=3)
        a = np.arange(3, dtype=np.int32)   # fits one 3-token budget
        ra = srv.submit(a, max_new_tokens=20)
        srv.step()                       # a admitted + decoding
        st_a = next(s for s in srv._slots if s is not None)
        assert srv._active.any()
        b = (np.arange(24, dtype=np.int32) * 3) % 16   # long prompt
        rb = srv.submit(b, max_new_tokens=4)
        ticks_while_b_prefills = 0
        while any(s is not None and s.phase == "prefill"
                  for s in srv._slots) or srv._queue:
            before = len(st_a.emitted)
            srv.step()
            ticks_while_b_prefills += 1
            assert len(st_a.emitted) == before + 1, \
                "in-flight decode starved by prefill work"
            assert ticks_while_b_prefills < 50
        # 24 tokens at 3/tick: b's prefill really did span many ticks
        assert ticks_while_b_prefills >= 8
        outs = srv.run()
        np.testing.assert_array_equal(outs[rb], stub_tokens(b, 4))
        np.testing.assert_array_equal(outs[ra], stub_tokens(a, 20))

    def test_multiple_admissions_one_tick(self):
        """Several queued requests are admitted and prefilled in the
        SAME tick (one ragged launch), not serialized one per tick."""
        srv = _stub_srv(max_slots=4)
        prompts = [np.arange(5, dtype=np.int32) + i for i in range(4)]
        rids = [srv.submit(p, max_new_tokens=3) for p in prompts]
        srv.step()
        assert int(srv._active.sum()) == 4          # all admitted
        assert srv.stats["admissions"] == 4
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], stub_tokens(p, 3))

    def test_admission_cap_limits_reservations_per_pass(self):
        srv = _stub_srv(max_slots=4, max_admissions_per_tick=1)
        for i in range(3):
            srv.submit(np.arange(4, dtype=np.int32) + i,
                       max_new_tokens=2)
        srv.step()
        # two scheduling passes per tick, capped at 1 admission each
        assert srv.stats["admissions"] + len(srv._prefill_fifo) <= 2
        srv.run()

    def test_full_prefix_hit_capped_at_t_minus_1(self):
        """Regression (ISSUE 6 satellite): a prompt FULLY covered by
        cached pages (page-aligned replay) still leaves >= 1 remainder
        token so the ragged launch emits its first-token logits."""
        srv = _stub_srv(max_slots=1)
        p = np.arange(8, dtype=np.int32)         # exactly 2 full pages
        for _ in range(2):
            rid = srv.submit(p, max_new_tokens=4)
            np.testing.assert_array_equal(srv.run()[rid],
                                          stub_tokens(p, 4))
        # replay hit is trimmed to one page: 4 reused + 4 re-prefilled
        assert srv.stats["prefix_auto_hits"] == 1
        assert srv.stats["prefix_auto_hit_tokens"] == 4

    def test_cancel_and_deadline_mid_prefill_leak_free(self):
        from paddle_tpu.telemetry.clock import FakeClock
        fc = FakeClock()
        srv = _stub_srv(max_slots=1, prefill_tokens_per_tick=2,
                        clock=fc)
        usable = srv._kv.num_pages - 1
        long_p = (np.arange(20, dtype=np.int32) * 5) % 16
        ra = srv.submit(long_p, max_new_tokens=4)
        srv.step()                               # mid-prefill
        st = next(s for s in srv._slots if s is not None)
        assert st.phase == "prefill"
        assert srv.cancel(ra) is True
        assert np.asarray(srv._results[ra]).size == 0   # empty partial
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0 and free + cached == usable

        rb = srv.submit(long_p, max_new_tokens=4, deadline_s=5.0)
        srv.step()
        fc.advance(10.0)                         # expire mid-prefill
        srv.step()
        free, live, pinned, cached = srv.pool_balance()
        assert live == 0 and free + cached == usable
        assert np.asarray(srv._results[rb]).size == 0
        # the pool still serves afterwards
        rc = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        np.testing.assert_array_equal(
            srv.run()[rc], stub_tokens(np.arange(4, dtype=np.int32), 3))

    def test_donation_of_partial_prefill_is_prefix_only(self):
        """A slot torn down mid-prefill donates ONLY the pages it
        actually wrote — a later identical prompt must not reuse
        unwritten pages (it would emit garbage if it did)."""
        srv = _stub_srv(max_slots=1, prefill_tokens_per_tick=5)
        p = (np.arange(16, dtype=np.int32) * 7) % 16
        ra = srv.submit(p, max_new_tokens=4)
        srv.step()                               # 5 of 16 rows written
        srv.cancel(ra)
        cached_after = srv._prefix.cached_pages
        assert cached_after <= 5 // srv._kv.page_size
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 4))

    def test_ragged_ignores_prefill_chunk_pad_bound(self):
        """Satellite: submit()'s fits-check must not charge the dense
        remainder chunk pad in ragged mode — a prompt that only fits
        unpadded is accepted and served."""
        srv = _stub_srv(max_slots=1, max_cache_len=32, prefill_chunk=8)
        p = (np.arange(29, dtype=np.int32) * 3) % 16   # pad would be 3
        rid = srv.submit(p, max_new_tokens=3)          # 29 + 3 == 32
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 3))
        with pytest.raises(ValueError, match="max_cache_len"):
            srv.submit(p, max_new_tokens=4)            # 29 + 4 > 32

    def test_submit_counts_pinned_sharing_in_fit_check(self):
        """Review regression: a request that only fits the pool by
        sharing a PINNED (register_prefix) page run must be accepted in
        ragged mode — the submit-time fit check counts the stable
        pinned run, not the raw full extent."""
        srv = _stub_srv(max_slots=1, max_cache_len=32, num_pages=9)
        prefix = (np.arange(16, dtype=np.int32) * 3) % 16
        srv.register_prefix(prefix)          # pins 4 of 8 usable pages
        p = np.concatenate([prefix,
                            np.asarray([1, 2, 3, 4], np.int32)])
        # extent 20 + 8 = 28 tokens = 7 pages; only 4 are unpinned, but
        # the pinned 4-page run is shared by reference
        rid = srv.submit(p, max_new_tokens=8)
        np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 8))
        # a request that can NEVER fit still fails fast
        q = (np.arange(24, dtype=np.int32) * 5) % 16   # no shared run
        with pytest.raises(ValueError, match="grow num_pages"):
            srv.submit(q, max_new_tokens=8)

    def test_admission_cap_applies_in_dense_mode_too(self):
        """Review regression: max_admissions_per_tick must not be an
        inert switch under prefill_mode='dense'."""
        srv = _stub_srv(max_slots=4, prefill_mode="dense",
                        max_admissions_per_tick=1)
        for i in range(4):
            srv.submit(np.arange(4, dtype=np.int32) + i,
                       max_new_tokens=2)
        srv.step()
        assert srv.stats["admissions"] <= 2    # two capped passes
        srv.run()

    def test_config_guards(self):
        with pytest.raises(ValueError, match="max_admissions_per_tick"):
            _stub_srv(max_admissions_per_tick=0)
        with pytest.raises(ValueError, match="prefill_mode"):
            _stub_srv(prefill_mode="bogus")
        with pytest.raises(ValueError, match="ragged"):
            ContinuousBatchingServer(StubModel(), max_cache_len=32,
                                     prefill_mode="ragged")
        with pytest.raises(ValueError, match="prefill_tokens_per_tick"):
            _stub_srv(prefill_tokens_per_tick=0)
        # a paged bundle without the ragged entry falls back to dense
        class OldStub(StubModel):
            def _decode_bundle(self, *a, **kw):
                return StubModel._decode_bundle(self, *a, **kw)[:5]

        srv = ContinuousBatchingServer(OldStub(), max_cache_len=32,
                                       cache_backend="paged",
                                       page_size=4)
        assert srv.prefill_mode == "dense"
