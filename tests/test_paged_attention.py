"""Paged KV cache + ragged paged-attention decode (PAPERS.md "Ragged
Paged Attention"): the Pallas kernel must match a naive gather oracle in
interpret mode, the XLA fallback must be BITWISE identical to the dense
decode attention, the page allocator must balance its books across slot
churn and prefix sharing, and ``ContinuousBatchingServer(
cache_backend="paged")`` must emit bit-identical tokens to the dense
backend (greedy and seeded sampling, mixed lengths, slot refill,
prefix-cache hits)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.kv_cache import OutOfPages, PagedKVCache
from paddle_tpu.ops.pallas import paged_attention as pa


def _rand(*shape, seed=0, scale=0.5):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(21)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _solo(model, ids, n_new, **kw):
    out = model.generate(pt.to_tensor(ids[None]), max_new_tokens=n_new,
                         max_cache_len=64, **kw).numpy()[0]
    return out[len(ids):]


def _static_grid_attention(q, k_pages, v_pages, block_tables, lengths,
                           sm_scale, layer=None):
    """The decode kernel as it stood before its grid followed the live
    pages: a static ``(slots, pages_per_slot)`` sweep, every page's step
    taken and the dead ones skipped by a ``pl.when``. The test's local
    oracle for "bit for bit on the live rows": same page-at-a-time
    online softmax, same order within a slot."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k_pages, v_pages, layer = pa.as_layered(k_pages, v_pages, layer)
    S, nh, hd = q.shape
    _, _, pg, width = k_pages.shape
    kvh, maxp = width // hd, block_tables.shape[1]
    rep = nh // kvh

    def kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr):
        s, p = pl.program_id(0), pl.program_id(1)

        @pl.when(p == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, pa.NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        length = len_ref[s]

        @pl.when(p * pg < length)
        def _compute():
            q = q_ref[0].astype(jnp.float32)
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)
            m_prev, l_prev = m_scr[:], l_scr[:]
            col = p * pg + jax.lax.broadcasted_iota(jnp.int32, (nh, pg), 1)
            valid = col < length
            s_log = jnp.concatenate([jax.lax.dot_general(
                q[g * rep:(g + 1) * rep], k[:, g * hd:(g + 1) * hd],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                for g in range(kvh)], axis=0) * sm_scale
            s_log = jnp.where(valid, s_log, pa.NEG_INF)
            m_new = jnp.maximum(m_prev[:, :1],
                                jnp.max(s_log, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev[:, :1] - m_new)
            pexp = jnp.where(valid, jnp.exp(s_log - m_new), 0.0)
            l_scr[:] = jnp.broadcast_to(
                corr * l_prev[:, :1] + jnp.sum(pexp, -1, keepdims=True),
                l_scr.shape)
            acc_scr[:] = acc_scr[:] * corr + jnp.concatenate([
                jax.lax.dot_general(
                    pexp[g * rep:(g + 1) * rep], v[:, g * hd:(g + 1) * hd],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                for g in range(kvh)], axis=0)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

        @pl.when(p == maxp - 1)
        def _finalize():
            l = l_scr[:, :1]
            o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)

    def page(s, p, bt, ln, l):
        return (l[0], bt[s * maxp + p], 0, 0)

    row = lambda s, p, bt, ln, l: (s, 0, 0)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, maxp),
            in_specs=[pl.BlockSpec((1, nh, hd), row),
                      pl.BlockSpec((1, 1, pg, width), page),
                      pl.BlockSpec((1, 1, pg, width), page)],
            out_specs=pl.BlockSpec((1, nh, hd), row),
            scratch_shapes=[pltpu.VMEM((nh, 128), jnp.float32),
                            pltpu.VMEM((nh, 128), jnp.float32),
                            pltpu.VMEM((nh, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        interpret=True,
    )(block_tables.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), layer, q, k_pages, v_pages)


# live patterns of one decode call: 5 slots, page 8, 4 pages a slot
_PG, _MAXP = 8, 4
_LIVE = {
    "none": [0, 0, 0, 0, 0],
    "one-slot": [0, 0, 13, 0, 0],
    "first-and-last": [9, 0, 0, 0, _MAXP * _PG],
    "full-house": [_MAXP * _PG] * 5,
    "page-edges": [1, _PG, _PG + 1, _MAXP * _PG, 0],
}


def _live_case(lengths, kvh, hd, layered):
    """Pools, block tables (distinct live pages, tails on a LOUD null
    page, as the allocator leaves them) and lengths of one pattern."""
    lengths = np.asarray(lengths, np.int32)
    S, P = len(lengths), 1 + len(lengths) * _MAXP
    kp = _rand(P, _PG, kvh, hd, seed=22).at[0].set(1e3)
    vp = _rand(P, _PG, kvh, hd, seed=23).at[0].set(-1e3)
    free = np.random.RandomState(24).permutation(np.arange(1, P))
    bt = np.zeros((S, _MAXP), np.int32)
    for s_, n in enumerate(-(-lengths // _PG)):
        bt[s_, :n] = free[s_ * _MAXP:s_ * _MAXP + n]
    layer = None
    if layered:        # the serving loop's call: whole pool, layer index
        from paddle_tpu.models.generation import pool_lanes
        kp, vp = (pool_lanes(jnp.stack([-a, a])) for a in (kp, vp))
        layer = 1
    return kp, vp, jnp.asarray(bt), jnp.asarray(lengths), layer


# ------------------------------------------------------------- kernel


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("kvh,nh", [(2, 2), (2, 4)])  # MHA and GQA
    def test_kernel_matches_gather_oracle(self, kvh, nh):
        S, hd, P, pg, maxp = 4, 32, 12, 8, 4
        q = _rand(S, nh, hd, seed=1)
        kp = _rand(P, pg, kvh, hd, seed=2)
        vp = _rand(P, pg, kvh, hd, seed=3)
        rng = np.random.RandomState(4)
        bt = jnp.asarray(np.stack([
            rng.choice(np.arange(1, P), maxp, replace=False)
            for _ in range(S)]).astype(np.int32))
        # ragged: page-boundary, mid-page, single-token, full lengths
        lengths = jnp.asarray(np.array([pg, 13, 1, maxp * pg], np.int32))
        out = pa._paged_attention_pallas(q, kp, vp, bt, lengths,
                                         1.0 / np.sqrt(hd),
                                         interpret=True)
        ref = pa._ref_paged_attention(q, kp, vp, bt, lengths,
                                      1.0 / np.sqrt(hd))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("pattern", list(_LIVE))
    @pytest.mark.parametrize("layered", [False, True],
                             ids=["one-layer", "layered-pool"])
    @pytest.mark.parametrize("kvh,nh", [(2, 2), (2, 4)],
                             ids=["mha", "gqa"])
    def test_grid_over_live_pages_matches_oracles(self, kvh, nh, layered,
                                                  pattern):
        """The grid takes one step a live page; whatever is live, a
        live row comes out as the gather oracle has it and BIT FOR BIT
        as the static ``slots x pages`` sweep computed it, and a slot
        of length 0 is exact zeros."""
        hd = 32
        kp, vp, bt, lengths, layer = _live_case(_LIVE[pattern], kvh, hd,
                                                layered)
        q = _rand(len(_LIVE[pattern]), nh, hd, seed=21)
        scale = 1.0 / np.sqrt(hd)
        out = np.asarray(pa._paged_attention_pallas(
            q, kp, vp, bt, lengths, scale, interpret=True, layer=layer))
        live = np.asarray(lengths) > 0
        assert (out[~live] == 0).all()
        old = np.asarray(_static_grid_attention(q, kp, vp, bt, lengths,
                                                scale, layer=layer))
        np.testing.assert_array_equal(out[live], old[live])
        ref = np.asarray(pa._ref_paged_attention(q, kp, vp, bt, lengths,
                                                 scale, layer=layer))
        np.testing.assert_allclose(out[live], ref[live], rtol=2e-5,
                                   atol=2e-5)

    @pytest.mark.parametrize("pattern", list(_LIVE))
    def test_grid_takes_the_steps_the_host_counts(self, pattern,
                                                  monkeypatch):
        """The bound the ``pallas_call`` is handed equals what
        ``decode_grid`` makes of the same lengths in NumPy (the
        server's ``decode_grid_steps``), and the schedule's first
        ``steps`` entries are the live pages, slot-major, each once."""
        from jax.experimental import pallas as pl
        lens = np.asarray(_LIVE[pattern], np.int32)
        pages, steps = pa.decode_grid(lens, _PG)
        np.testing.assert_array_equal(pages, -(-lens // _PG))
        assert int(steps) == max(int(pages.sum()), 1)
        want = [s_ * _MAXP + p for s_, n in enumerate(pages)
                for p in range(n)]
        entries, dev_steps = pa.decode_schedule(jnp.asarray(lens), _PG,
                                                _MAXP)
        entries = np.asarray(entries)
        assert int(dev_steps) == int(steps)
        assert entries[:len(want)].tolist() == want
        # past the live pages: valid block-table indices nobody visits
        assert entries.shape == (len(lens) * _MAXP,)
        assert ((0 <= entries) & (entries < len(lens) * _MAXP)).all()

        seen = []
        real = pl.pallas_call

        def spy(kernel, *a, grid_spec, **kw):
            seen.append(int(grid_spec.grid[0]))
            return real(kernel, *a, grid_spec=grid_spec, **kw)

        monkeypatch.setattr(pl, "pallas_call", spy)
        kp, vp, bt, lengths, _ = _live_case(lens, 2, 32, False)
        pa._paged_attention_pallas(_rand(len(lens), 2, 32, seed=21), kp, vp,
                                   bt, lengths, 0.2, interpret=True)
        assert seen == [int(steps)]

    def test_kernel_ignores_stale_tail_pages(self):
        """Block-table entries past a slot's length point at the null
        page (or stale pages); their contents must not leak into the
        output."""
        S, nh, kvh, hd, P, pg, maxp = 2, 2, 2, 32, 8, 8, 3
        q = _rand(S, nh, hd, seed=5)
        kp = _rand(P, pg, kvh, hd, seed=6)
        vp = _rand(P, pg, kvh, hd, seed=7)
        bt = jnp.asarray(np.array([[1, 0, 0], [2, 3, 0]], np.int32))
        lengths = jnp.asarray(np.array([5, 11], np.int32))
        out1 = pa._paged_attention_pallas(q, kp, vp, bt, lengths, 0.2,
                                          interpret=True)
        # poison everything the lengths say is invalid
        kp2 = kp.at[0].set(1e3).at[4:].set(-1e3)
        vp2 = vp.at[0].set(1e3).at[4:].set(-1e3)
        kp2 = kp2.at[1, 5:].set(77.0)        # slot 0 rows past length 5
        vp2 = vp2.at[1, 5:].set(77.0)
        kp2 = kp2.at[3, 3:].set(-77.0)       # slot 1 rows past 11 = 8+3
        vp2 = vp2.at[3, 3:].set(-77.0)
        out2 = pa._paged_attention_pallas(q, kp2, vp2, bt, lengths, 0.2,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_ref_path_bitwise_matches_dense_attend(self):
        """The gather fallback mirrors generation._cached_attend op for
        op — paging a dense cache must not change a single bit."""
        from paddle_tpu.models.generation import _cached_attend
        B, nh, kvh, hd, T, pg = 3, 4, 2, 16, 32, 8
        maxp = T // pg
        q = _rand(B, 1, nh, hd, seed=8)
        kc = _rand(B, T, kvh, hd, seed=9)
        vc = _rand(B, T, kvh, hd, seed=10)
        t = jnp.asarray(np.array([4, 17, 31], np.int32))   # lengths-1
        kk = jnp.repeat(kc, nh // kvh, axis=2)
        vv = jnp.repeat(vc, nh // kvh, axis=2)
        want = _cached_attend(q, kk, vv, t, 1, 0.25)       # [B,1,nh,hd]

        # page the dense cache: slot b gets pages [1+b*maxp, ...)
        P = 1 + B * maxp
        kp = jnp.zeros((P, pg, kvh, hd), jnp.float32)
        vp = jnp.zeros((P, pg, kvh, hd), jnp.float32)
        bt = np.zeros((B, maxp), np.int32)
        for b in range(B):
            ids = 1 + b * maxp + np.arange(maxp)
            bt[b] = ids
            kp = kp.at[ids].set(kc[b].reshape(maxp, pg, kvh, hd))
            vp = vp.at[ids].set(vc[b].reshape(maxp, pg, kvh, hd))
        got = pa._ref_paged_attention(q[:, 0], kp, vp, jnp.asarray(bt),
                                      t + 1, 0.25)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want[:, 0]))

    @pytest.mark.parametrize("path", ["interpret", "fallback"])
    def test_idle_rows_cost_the_live_rows_nothing(self, path):
        """A slot at length 0 (the idle sentinel, as ``_paged_attend``
        hands it over) attends over nothing: the kernel skips its every
        page and writes zeros, the fallback masks every position and
        stays finite. The live rows come out bit for bit as from a call
        that holds no idle row."""
        nh, kvh, hd, P, pg, maxp = 4, 2, 32, 12, 8, 4
        q = _rand(5, nh, hd, seed=11)
        kp = _rand(P, pg, kvh, hd, seed=12)
        vp = _rand(P, pg, kvh, hd, seed=13)
        # the null page holds whatever parked rows wrote: make it loud
        kp, vp = kp.at[0].set(1e3), vp.at[0].set(-1e3)
        rng = np.random.RandomState(14)
        bt = np.stack([rng.choice(np.arange(1, P), maxp, replace=False)
                       for _ in range(5)]).astype(np.int32)
        idle = np.array([True, False, True, False, True])
        bt[idle] = 0                       # an idle slot's table: null
        lengths = np.where(idle, 0, [0, 13, 0, maxp * pg, 0]).astype(
            np.int32)

        def run(rows):
            args = (q[rows], kp, vp, jnp.asarray(bt[rows]),
                    jnp.asarray(lengths[rows]), 1.0 / np.sqrt(hd))
            if path == "interpret":
                return np.asarray(pa._paged_attention_pallas(
                    *args, interpret=True))
            return np.asarray(pa._ref_paged_attention(*args))

        out = run(np.arange(5))
        np.testing.assert_array_equal(out[~idle], run(np.where(~idle)[0]))
        if path == "interpret":
            assert (out[idle] == 0).all()
        else:
            assert np.isfinite(out[idle]).all()

    def test_paged_attend_hands_parked_slots_length_zero(self, monkeypatch):
        """``generation._paged_attend``: a slot whose ``t`` sits at or
        past the table's span gets length 0, every other ``t + 1``."""
        from paddle_tpu.models import generation
        L, P, pg, kvh, hd, maxp = 2, 9, 8, 2, 16, 4
        pool = {"k": _rand(L, P, pg, kvh * hd, seed=15),
                "v": _rand(L, P, pg, kvh * hd, seed=16)}
        bt = jnp.asarray(np.array([[1, 2, 3, 4], [0] * 4, [5, 6, 7, 8],
                                   [0] * 4], np.int32))
        t = jnp.asarray(np.array([9, maxp * pg, 30, maxp * pg + 7],
                                 np.int32))
        q = _rand(4, 1, 4, hd, seed=17)
        seen = {}
        real = pa.paged_attention

        def spy(q, k, v, bt, lengths, *a, **kw):
            seen["lengths"] = np.asarray(lengths)
            return real(q, k, v, bt, lengths, *a, **kw)

        monkeypatch.setattr(pa, "paged_attention", spy)
        out = generation._paged_attend(q, pool, 1, bt, t, 0.25)
        np.testing.assert_array_equal(seen["lengths"], [10, 0, 31, 0])
        assert out.shape == (4, 1, 4, hd) and np.isfinite(
            np.asarray(out)).all()


@pytest.mark.slow
class TestPagedAttentionOnChip:
    """Compiled (non-interpret) kernel path — needs a real TPU backend;
    CPU tier-1 covers the same math through interpret mode above."""

    def test_compiled_kernel_matches_oracle(self):
        if not pa.available():
            pytest.skip("needs a TPU backend")
        S, nh, kvh, hd, P, pg, maxp = 8, 8, 2, 128, 64, 32, 8
        q = _rand(S, nh, hd, seed=1)
        kp = _rand(P, pg, kvh, hd, seed=2)
        vp = _rand(P, pg, kvh, hd, seed=3)
        rng = np.random.RandomState(4)
        bt = jnp.asarray(np.stack([
            rng.choice(np.arange(1, P), maxp, replace=False)
            for _ in range(S)]).astype(np.int32))
        lengths = jnp.asarray(
            rng.randint(1, maxp * pg + 1, (S,)).astype(np.int32))
        out = pa.paged_attention(q, kp, vp, bt, lengths)
        ref = pa._ref_paged_attention(q, kp, vp, bt, lengths,
                                      1.0 / np.sqrt(hd))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------ allocator


class TestPagedKVCache:
    def test_alloc_free_lifecycle_and_null_page(self):
        kv = PagedKVCache(num_pages=9, page_size=4, max_slots=2,
                          pages_per_slot=4)
        assert kv.free_pages() == 8            # page 0 reserved
        own = kv.admit_slot(0, 10)             # ceil(10/4) = 3 pages
        assert len(own) == 3 and 0 not in own
        assert kv.coverage(0) == 12
        assert (kv.block_table[0, :3] == own).all()
        assert (kv.block_table[0, 3:] == 0).all()
        assert kv.used_pages() == 3
        kv.free_slot(0)
        assert kv.used_pages() == 0 and kv.free_pages() == 8
        assert (kv.block_table[0] == 0).all()

    def test_out_of_pages_and_oversubscription(self):
        kv = PagedKVCache(num_pages=5, page_size=4, max_slots=2,
                          pages_per_slot=4)
        kv.admit_slot(0, 12)                   # 3 of 4 pages
        with pytest.raises(OutOfPages):
            kv.admit_slot(1, 8)                # needs 2, only 1 free
        kv.free_slot(0)
        kv.admit_slot(1, 8)                    # now fits
        with pytest.raises(ValueError):
            kv.admit_slot(0, 17)               # > pages_per_slot

    def test_shared_prefix_pages_refcounted(self):
        kv = PagedKVCache(num_pages=12, page_size=4, max_slots=3,
                          pages_per_slot=4)
        shared = kv.alloc(2)                   # registry holds one ref
        base_used = kv.used_pages()
        kv.admit_slot(0, 12, shared_pages=shared)
        kv.admit_slot(1, 10, shared_pages=shared)
        # 2 shared (stored once) + 1 own each
        assert kv.used_pages() == base_used + 2
        assert list(kv.block_table[0, :2]) == shared
        assert list(kv.block_table[1, :2]) == shared
        kv.free_slot(0)
        kv.free_slot(1)
        # registry ref keeps the shared pages alive
        assert kv.used_pages() == base_used == 2

    def test_hbm_accounting(self):
        paged = PagedKVCache.paged_hbm_bytes(num_pages=65, page_size=16,
                                             layers=2, kv_heads=2,
                                             head_dim=32, itemsize=4)
        dense = PagedKVCache.dense_hbm_bytes(max_slots=8,
                                             max_cache_len=1024,
                                             layers=2, kv_heads=2,
                                             head_dim=32, itemsize=4)
        assert paged * 7 < dense               # ~8x smaller pool


# -------------------------------------------------------------- server


class TestPagedServer:
    def _both(self, model, prompts, n_new, page_size=8, num_pages=None,
              **kw):
        """Run the same workload through dense and paged servers and
        assert bit-identical per-request tokens."""
        dense = ContinuousBatchingServer(model, max_slots=2,
                                         max_cache_len=64, **kw)
        paged = ContinuousBatchingServer(model, max_slots=2,
                                         max_cache_len=64,
                                         cache_backend="paged",
                                         page_size=page_size,
                                         num_pages=num_pages, **kw)
        seeds = list(range(100, 100 + len(prompts)))
        rd = [dense.submit(p, max_new_tokens=n_new, seed=s)
              for p, s in zip(prompts, seeds)]
        rp = [paged.submit(p, max_new_tokens=n_new, seed=s)
              for p, s in zip(prompts, seeds)]
        od, op = dense.run(), paged.run()
        for a, b in zip(rd, rp):
            np.testing.assert_array_equal(od[a], op[b])
        return paged

    def test_greedy_parity_with_slot_refill(self):
        model = _model()
        rng = np.random.default_rng(0)
        # 5 requests through 2 slots: refill mid-run, mixed lengths
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (3, 9, 5, 12, 4)]
        srv = self._both(model, prompts, 6)
        # every page is either back on the free list or held by the
        # auto prefix cache (the 9- and 12-token prompts each donated
        # one full page); none is leaked to a dead slot
        free, live, pinned, cached = srv.pool_balance()
        assert (live, pinned, cached) == (0, 0, 2)

    def test_sampled_parity_seeded(self):
        model = _model()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 6, 5)]
        self._both(model, prompts, 7, do_sample=True, temperature=1.3,
                   top_k=9)

    def test_tick_block_parity(self):
        model = _model()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 7, 5)]
        self._both(model, prompts, 7, tick_block=4)

    def test_small_pool_defers_admission_with_parity(self):
        """A pool too small for every request at once: admission waits
        for pages without changing any tokens."""
        model = _model()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 6, 5, 3)]
        # room for ~1.5 slots' worth of pages (64-token budget = 8 pages)
        srv = self._both(model, prompts, 6, num_pages=13)
        assert srv._kv.used_pages() == 0

    def test_admission_reserves_full_extent_no_midrun_oom(self):
        """Admission reserves prompt + budget pages, so a pool with room
        for the prompts of two slots but not their decode growth admits
        ONE at a time instead of crashing OutOfPages mid-decode."""
        model = _model()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 256, (8,)).astype(np.int32)
                   for _ in range(2)]
        # extent 8 + 48 = 56 tokens = 7 pages per request; 12 usable
        # pages hold one reservation, not two
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8, num_pages=13)
        rids = [srv.submit(p, max_new_tokens=48) for p in prompts]
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _solo(model, p, 48))
        free, live, pinned, cached = srv.pool_balance()
        assert (live, cached) == (0, 2)        # one donated page each

    def test_tick_block_tight_pool_no_midstep_alloc(self):
        """tick_block > 1 on a pool with zero spare pages: block steps
        past a slot's budget go to the null page and must not try to
        allocate coverage (would OutOfPages on a legally sized pool)."""
        model = _model()
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 256, (8,)).astype(np.int32)
                   for _ in range(2)]
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8, num_pages=5,
                                       tick_block=16)
        rids = [srv.submit(p, max_new_tokens=2) for p in prompts]
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _solo(model, p, 2))
        free, live, pinned, cached = srv.pool_balance()
        assert (live, cached) == (0, 2)        # one donated page each

    def test_register_prefix_refuses_to_strand_queued_request(self):
        """Pinning prefix pages after a submit must not silently starve
        the queue: a registration that makes a queued request forever
        unadmittable is rejected (and rolled back)."""
        model = _model()
        rng = np.random.default_rng(7)
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8, num_pages=9)
        # queued head needs all 8 usable pages
        srv.submit(rng.integers(0, 256, (8,)).astype(np.int32),
                   max_new_tokens=56)
        prefix = rng.integers(0, 256, (16,)).astype(np.int32)
        with pytest.raises(ValueError, match="strand"):
            srv.register_prefix(prefix)
        assert srv._kv.used_pages() == 0       # rollback complete
        assert srv._prefixes == []
        srv.run()                              # queued request unharmed

    def test_prefix_pages_shared_once_with_parity(self):
        model = _model()
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, 256, (10,)).astype(np.int32)
        tails = [rng.integers(0, 256, (n,)).astype(np.int32)
                 for n in (3, 5)]
        prompts = [np.concatenate([prefix, t]) for t in tails]

        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8)
        srv.register_prefix(prefix)
        # the 10-token prefix pins exactly one full 8-token page;
        # re-registering (client retry) is an idempotent no-op
        assert srv._kv.used_pages() == 1
        assert srv.register_prefix(prefix) == 10
        assert srv._kv.used_pages() == 1 and len(srv._prefixes) == 1
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _solo(model, p, 6))
        # RAGGED prefill (the paged default, ISSUE 6): registered hits
        # reuse the prefix's page-aligned run through the radix tree —
        # the 10-token prefix pins one full 8-token page, so each
        # request reuses 8 tokens and re-prefills its 2-token sub-page
        # tail with the remainder (recomputation is deterministic;
        # tokens stay bit-identical, asserted above). The PR-5 dense
        # path (prefill_mode="dense") seeded the exact 10 rows instead:
        # 20 hit tokens / 18 prefill — the page-granular accounting is
        # the deliberate ISSUE-6 contract for ragged mode.
        assert srv.stats["prefix_hit_tokens"] == 2 * 8
        assert srv.stats["prefill_tokens"] == 10 + (2 + 3) + (2 + 5)
        assert srv._kv.used_pages() == 1

    def test_eos_frees_pages_early(self):
        model = _model()
        rng = np.random.default_rng(2)
        p = rng.integers(0, 256, (4,)).astype(np.int32)
        solo = _solo(model, p, 8)
        eos = int(solo[2])
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8, eos_token_id=eos)
        rid = srv.submit(p, max_new_tokens=8)
        out = srv.run()[rid]
        np.testing.assert_array_equal(out, solo[:len(out)])
        assert srv._kv.used_pages() == 0

    def test_cancel_mid_flight_frees_pages(self):
        model = _model()
        rng = np.random.default_rng(11)
        a = rng.integers(0, 256, (4,)).astype(np.int32)
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8)
        ra = srv.submit(a, max_new_tokens=10)
        for _ in range(3):
            srv.step()
        assert srv._kv.used_pages() > 0
        assert srv.cancel(ra) is True
        srv.run()
        assert srv._kv.used_pages() == 0

    def test_gpt_and_mixtral_paged_parity(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        rng = np.random.default_rng(8)
        pt.seed(22)
        g = GPTForCausalLM(gpt2_tiny())
        g.eval()
        p = rng.integers(0, g.cfg.vocab_size, (4,)).astype(np.int32)
        srv = ContinuousBatchingServer(g, max_slots=2, max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=16)
        rid = srv.submit(p, max_new_tokens=5)
        np.testing.assert_array_equal(srv.run()[rid], _solo(g, p, 5))

        pt.seed(24)
        moe = MixtralForCausalLM(mixtral_tiny())
        moe.eval()
        p = rng.integers(0, 256, (5,)).astype(np.int32)
        srv = ContinuousBatchingServer(moe, max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8)
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], _solo(moe, p, 4))

    def test_config_guards(self):
        model = _model()
        with pytest.raises(ValueError, match="divide max_cache_len"):
            ContinuousBatchingServer(model, max_cache_len=64,
                                     cache_backend="paged", page_size=7)
        with pytest.raises(ValueError, match="cache_backend"):
            ContinuousBatchingServer(model, cache_backend="ragged")
        with pytest.raises(NotImplementedError):
            ContinuousBatchingServer(model, max_cache_len=64,
                                     cache_backend="paged", page_size=8,
                                     cache_dtype="int8")
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       cache_backend="paged",
                                       page_size=8, num_pages=3)
        with pytest.raises(ValueError, match="grow num_pages"):
            srv.submit(np.zeros((20,), np.int32), max_new_tokens=4)
