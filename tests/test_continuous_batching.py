"""Continuous-batching server (inference/continuous_batching.py): results
for every request must equal a solo model.generate() run — slots are
row-wise independent, so batching and mid-flight admission cannot change
tokens."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer


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


class TestContinuousBatching:
    def test_more_requests_than_slots_match_solo(self):
        model = _model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (3, 5, 4)]
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = srv.run()
        assert set(outs) == set(rids)
        for rid, prompt in zip(rids, prompts):
            want = _solo(model, prompt, 6)
            np.testing.assert_array_equal(outs[rid], want)

    def test_mid_flight_admission_does_not_disturb(self):
        model = _model()
        rng = np.random.default_rng(1)
        a = rng.integers(0, 256, (4,)).astype(np.int32)
        b = rng.integers(0, 256, (6,)).astype(np.int32)
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64)
        ra = srv.submit(a, max_new_tokens=8)
        for _ in range(3):          # a is mid-decode when b arrives
            srv.step()
        rb = srv.submit(b, max_new_tokens=5)
        outs = srv.run()
        np.testing.assert_array_equal(outs[ra], _solo(model, a, 8))
        np.testing.assert_array_equal(outs[rb], _solo(model, b, 5))

    def test_eos_frees_slot_early(self):
        model = _model()
        rng = np.random.default_rng(2)
        p = rng.integers(0, 256, (4,)).astype(np.int32)
        solo = _solo(model, p, 8)
        eos = int(solo[2])          # third generated token acts as eos
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       eos_token_id=eos)
        rid = srv.submit(p, max_new_tokens=8)
        out = srv.run()[rid]
        assert out[-1] == eos and len(out) <= 8
        np.testing.assert_array_equal(out, solo[:len(out)])

    def test_length_guard_and_batch_submit_rejected(self):
        model = _model()
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=16)
        with pytest.raises(ValueError, match="max_cache_len"):
            srv.submit(np.zeros((12,), np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="one request"):
            srv.submit(np.zeros((2, 4), np.int32))
        # chunk-pad overflow must be rejected AT SUBMIT (not lost later
        # inside step(): code-review r5)
        srv2 = ContinuousBatchingServer(model, max_slots=1,
                                        max_cache_len=16,
                                        prefill_chunk=6)
        with pytest.raises(ValueError, match="pad rows"):
            srv2.submit(np.zeros((13,), np.int32), max_new_tokens=3)

    def test_tick_block_parity_greedy_and_sampled(self):
        """tick_block=4 (four decode steps per dispatch) changes neither
        greedy nor sampled tokens vs tick_block=1/solo."""
        model = _model()
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 6, 5)]
        for kw in (dict(), dict(do_sample=True, temperature=1.3,
                                top_k=9)):
            srv = ContinuousBatchingServer(model, max_slots=2,
                                           max_cache_len=64,
                                           tick_block=4, **kw)
            rids = [srv.submit(p, max_new_tokens=7, seed=200 + i)
                    for i, p in enumerate(prompts)]
            outs = srv.run()
            for i, (rid, p) in enumerate(zip(rids, prompts)):
                want = model.generate(
                    pt.to_tensor(p[None]), max_new_tokens=7,
                    seed=200 + i, max_cache_len=64,
                    **kw).numpy()[0, len(p):]
                np.testing.assert_array_equal(outs[rid], want)

    def test_tick_block_eos_mid_block(self):
        """A slot hitting eos inside a block stops there; trailing block
        tokens are discarded and the slot refills."""
        model = _model()
        rng = np.random.default_rng(7)
        p = rng.integers(0, 256, (4,)).astype(np.int32)
        solo = _solo(model, p, 8)
        # eos = a token whose FIRST occurrence is mid-sequence
        eos, cut = None, None
        for j in range(1, len(solo)):
            if solo[j] not in solo[:j]:
                eos, cut = int(solo[j]), j
        assert eos is not None, "degenerate sequence; change seed"
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64,
                                       eos_token_id=eos, tick_block=5)
        rid = srv.submit(p, max_new_tokens=8)
        rid2 = srv.submit(p, max_new_tokens=8)   # refills the same slot
        outs = srv.run()
        np.testing.assert_array_equal(outs[rid], solo[:cut + 1])
        np.testing.assert_array_equal(outs[rid2], solo[:cut + 1])

    def test_sampled_requests_match_solo_generate(self):
        """Per-request PRNG chains: submit(seed=s) draws exactly what a
        solo generate(do_sample=True, seed=s) draws, even with both
        slots mid-flight."""
        model = _model()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 6, 5)]
        kw = dict(do_sample=True, temperature=1.5, top_k=7)
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64, **kw)
        rids = [srv.submit(p, max_new_tokens=7, seed=100 + i)
                for i, p in enumerate(prompts)]
        outs = srv.run()
        for i, (rid, p) in enumerate(zip(rids, prompts)):
            want = model.generate(pt.to_tensor(p[None]), max_new_tokens=7,
                                  seed=100 + i, max_cache_len=64,
                                  **kw).numpy()[0, len(p):]
            np.testing.assert_array_equal(outs[rid], want)

    def test_prefix_cache_parity_and_savings(self):
        """Registered shared prefix: identical tokens, remainder-only
        prefill work."""
        model = _model()
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, 256, (10,)).astype(np.int32)
        tails = [rng.integers(0, 256, (n,)).astype(np.int32)
                 for n in (3, 5)]
        prompts = [np.concatenate([prefix, t]) for t in tails]

        plain = ContinuousBatchingServer(model, max_slots=2,
                                         max_cache_len=64)
        rids = [plain.submit(p, max_new_tokens=6) for p in prompts]
        want = plain.run()

        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64)
        srv.register_prefix(prefix)
        rids2 = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = srv.run()
        for ra, rb in zip(rids, rids2):
            np.testing.assert_array_equal(outs[rb], want[ra])
        # prefill work: 10 (register) + 3 + 5 vs 13 + 15
        assert srv.stats["prefill_tokens"] == 10 + 3 + 5
        assert srv.stats["prefix_hit_tokens"] == 20
        assert plain.stats["prefill_tokens"] == 13 + 15

    def test_prefix_exact_match_uses_stored_logits(self):
        """A prompt equal to the prefix itself prefills zero tokens."""
        model = _model()
        rng = np.random.default_rng(5)
        prefix = rng.integers(0, 256, (8,)).astype(np.int32)
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64)
        srv.register_prefix(prefix)
        base = srv.stats["prefill_tokens"]
        rid = srv.submit(prefix, max_new_tokens=5)
        out = srv.run()[rid]
        assert srv.stats["prefill_tokens"] == base   # no extra prefill
        want = _solo(model, prefix, 5)
        np.testing.assert_array_equal(out, want)

    def test_mixtral_and_int8_through_server(self):
        """The server is model-agnostic: MoE decode and weight-only int8
        both serve with solo-parity."""
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        pt.seed(24)
        moe = MixtralForCausalLM(mixtral_tiny())
        moe.eval()
        rng = np.random.default_rng(8)
        p = rng.integers(0, 256, (5,)).astype(np.int32)
        want = moe.generate(pt.to_tensor(p[None]), max_new_tokens=4,
                            max_cache_len=64).numpy()[0, 5:]
        srv = ContinuousBatchingServer(moe, max_slots=2, max_cache_len=64)
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], want)

        lm = _model()
        want8 = lm.generate(pt.to_tensor(p[None]), max_new_tokens=4,
                            max_cache_len=64,
                            weight_dtype="int8").numpy()[0, 5:]
        srv8 = ContinuousBatchingServer(lm, max_slots=1, max_cache_len=64,
                                        weight_dtype="int8")
        rid = srv8.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv8.run()[rid], want8)

    def test_streaming_chunks_concatenate_to_result(self):
        model = _model()
        rng = np.random.default_rng(10)
        p = rng.integers(0, 256, (4,)).astype(np.int32)
        chunks = []
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64, tick_block=3)
        rid = srv.submit(p, max_new_tokens=7,
                         on_token=lambda r, t: chunks.append((r, t)))
        out = srv.run()[rid]
        assert all(r == rid for r, _ in chunks)
        np.testing.assert_array_equal(
            np.concatenate([t for _, t in chunks]), out)
        assert len(chunks) >= 3       # admission token + >=2 blocks

    def test_cancel_queued_and_mid_flight(self):
        model = _model()
        rng = np.random.default_rng(11)
        a = rng.integers(0, 256, (4,)).astype(np.int32)
        b = rng.integers(0, 256, (5,)).astype(np.int32)
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64)
        ra = srv.submit(a, max_new_tokens=10)
        rb = srv.submit(b, max_new_tokens=5)
        assert srv.cancel(rb) is True          # still queued
        for _ in range(3):
            srv.step()                         # a is mid-decode
        assert srv.cancel(ra) is True
        outs = srv.run()
        assert rb not in outs
        partial = outs[ra]
        want = _solo(model, a, 10)
        assert 1 <= len(partial) < 10
        np.testing.assert_array_equal(partial, want[:len(partial)])
        assert srv.cancel(12345) is False

    def test_threaded_serving_solo_exact(self):
        """start() drives decode on a background thread; concurrent
        submitters get solo-exact results via wait()."""
        import threading
        model = _model()
        rng = np.random.default_rng(12)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (4, 6, 5, 7)]
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64).start()
        results = {}
        errs = []

        def client(i, p):
            try:
                rid = srv.submit(p, max_new_tokens=5)
                results[i] = srv.wait(rid, timeout=300)
            except Exception as e:     # surface in main thread
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        srv.stop()
        assert not errs, errs
        assert len(results) == 4
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(results[i], _solo(model, p, 5))

    def test_poisoned_callback_fails_only_its_request(self):
        """code-review r5 + PR 3 supervision: a crashing on_token
        callback must not wedge (or kill) the server — ITS waiter gets
        the typed error, and the server keeps serving new requests on
        the same thread."""
        from paddle_tpu.reliability import CallbackError
        model = _model()
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=64).start()
        rid = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=4,
                         on_token=lambda r, t: 1 / 0)
        with pytest.raises(CallbackError, match="on_token"):
            srv.wait(rid, timeout=60)
        # the serve thread survived: a fresh request completes normally
        p = np.arange(4, dtype=np.int32)
        rid2 = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.wait(rid2, timeout=300),
                                      _solo(model, p, 4))
        srv.stop()

    def test_everything_composed(self):
        """Kitchen sink: prefix cache + chunked prefill + tick_block +
        weight-only int8, all at once — still solo-parity."""
        model = _model()
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, 256, (8,)).astype(np.int32)
        tails = [rng.integers(0, 256, (n,)).astype(np.int32)
                 for n in (3, 6)]
        prompts = [np.concatenate([prefix, t]) for t in tails] + \
                  [rng.integers(0, 256, (5,)).astype(np.int32)]
        srv = ContinuousBatchingServer(
            model, max_slots=2, max_cache_len=64, weight_dtype="int8",
            prefill_chunk=4, tick_block=3)
        srv.register_prefix(prefix)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            want = model.generate(pt.to_tensor(p[None]),
                                  max_new_tokens=6, max_cache_len=64,
                                  weight_dtype="int8",
                                  prefill_chunk=4).numpy()[0, len(p):]
            np.testing.assert_array_equal(outs[rid], want)
        assert srv.stats["prefix_hit_tokens"] == 16

    def test_gpt_greedy_parity_through_server(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(22)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, model.cfg.vocab_size, (n,))
                   .astype(np.int32) for n in (3, 4)]
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64)
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        outs = srv.run()
        for rid, prompt in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid],
                                          _solo(model, prompt, 5))


# ------------------------------------------------------- the idle sentinel
CACHE = 64


def _stub_server(backend, tick_block, **kw):
    from _serving_stub import StubModel
    if backend == "paged":
        kw.setdefault("num_pages", 33)
        kw.update(cache_backend="paged", page_size=8)
    kw.setdefault("max_slots", 4)
    return ContinuousBatchingServer(StubModel(), max_cache_len=CACHE,
                                    tick_block=tick_block, **kw)


def _watch_dispatches(srv):
    """Record, at every decode dispatch, the ``t`` the program is handed
    and the host's active set."""
    seen = []
    real = srv._decode_jit = srv._build_decode_step()

    def spy(tok, caches, t, keys):
        seen.append((np.asarray(t).copy(), srv._active.copy()))
        return real(tok, caches, t, keys)

    srv._decode_jit = spy
    return seen


def _assert_parked(seen, tick_block, slots=4):
    """Every slot outside the active set rode parked, every slot in it
    at a real position; and the counters say the same."""
    assert seen
    for t, active in seen:
        assert (t[~active] >= CACHE).all(), (t, active)
        assert (t[active] < CACHE).all(), (t, active)
    return (len(seen) * slots * tick_block,
            sum(int(a.sum()) for _, a in seen) * tick_block)


@pytest.mark.parametrize("tick_block", [1, 4])
@pytest.mark.parametrize("backend", ["dense", "paged"])
class TestIdleSentinel:
    """A slot that holds no decoding request reads ``t >=
    max_cache_len`` on the device at every decode dispatch, whatever
    took it out of the active set; the tick programs read liveness off
    that (tests/test_paged_attention.py, tests/test_keye_vl.py)."""

    def test_never_used_finished_and_cancelled_slots(self, backend,
                                                     tick_block):
        from _serving_stub import stub_tokens
        srv = _stub_server(backend, tick_block)
        seen = _watch_dispatches(srv)
        # before anything was admitted every slot is parked
        assert (np.asarray(srv._t) == CACHE).all()
        prompts = [np.arange(3 + i, dtype=np.int32) % 16 for i in range(3)]
        short = srv.submit(prompts[0], max_new_tokens=3)
        gone = srv.submit(prompts[1], max_new_tokens=30)
        long = srv.submit(prompts[2], max_new_tokens=20)
        for _ in range(3):
            srv.step()
        assert srv.cancel(gone)            # mid-flight: slot 1 frees
        outs = srv.run()
        np.testing.assert_array_equal(outs[short],
                                      stub_tokens(prompts[0], 3))
        np.testing.assert_array_equal(outs[long],
                                      stub_tokens(prompts[2], 20))
        rows, live = _assert_parked(seen, tick_block)
        # slot 3 was never used; the finished and the cancelled slot
        # were seen parked while the long request still decoded
        assert all(t[3] >= CACHE for t, _ in seen)
        tail_t, tail_active = seen[-1]
        assert tail_active.sum() == 1 and (tail_t >= CACHE).sum() == 3
        s = srv.stats
        assert s["decode_ticks"] == len(seen)
        assert (s["decode_rows"], s["decode_live_rows"]) == (rows, live)
        assert 0 < live < rows
        # and once drained, every slot rests ON the sentinel (the last
        # park rides the state push of the next decode dispatch)
        srv._flush_slot_state()
        assert (np.asarray(srv._t) == CACHE).all()

    def test_parked_slot_stays_parked_and_refills_exactly(self, backend,
                                                          tick_block):
        """One slot, three tenants in turn: each decodes the tokens a
        fresh server gives it, and between them (and across many blocks
        of another slot's decoding) the parked ``t`` never moves."""
        from _serving_stub import stub_tokens
        srv = _stub_server(backend, tick_block, max_slots=2)
        seen = _watch_dispatches(srv)
        anchor = np.arange(5, dtype=np.int32)
        ra = srv.submit(anchor, max_new_tokens=40)   # keeps slot 0 busy
        tenants = [np.arange(4 + i, dtype=np.int32)[::-1] % 16
                   for i in range(3)]
        for p in tenants:
            rid = srv.submit(p, max_new_tokens=5)
            while rid not in srv._results:
                srv.step()
                if not srv._active[1]:
                    # parked exactly on the sentinel, not past it
                    srv._flush_slot_state()
                    assert int(np.asarray(srv._t)[1]) == CACHE
            np.testing.assert_array_equal(srv._results[rid],
                                          stub_tokens(p, 5))
        outs = srv.run()
        np.testing.assert_array_equal(outs[ra], stub_tokens(anchor, 40))
        _assert_parked(seen, tick_block, slots=2)


@pytest.mark.parametrize("tick_block", [1, 4])
def test_preempted_slot_is_parked(tick_block):
    """Optimistic admission over a pool too small: victims are torn
    down mid-decode, park on the sentinel, and replay bit-exactly."""
    from _serving_stub import stub_tokens
    srv = _stub_server("paged", tick_block, num_pages=9,
                       admission="optimistic")
    seen = _watch_dispatches(srv)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 16, (int(k),)).astype(np.int32)
               for k in rng.integers(4, 12, (8,))]
    rids = [srv.submit(p, max_new_tokens=28) for p in prompts]
    outs = srv.run()
    assert srv.stats["preemptions"] > 0
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], stub_tokens(p, 28))
    rows, live = _assert_parked(seen, tick_block)
    assert (srv.stats["decode_rows"], srv.stats["decode_live_rows"]) \
        == (rows, live)


@pytest.mark.parametrize("tick_block", [1, 4])
def test_decode_grid_counters_follow_the_dispatched_lengths(tick_block):
    """``decode_grid_steps`` / ``decode_live_pages``: what the host adds
    a tick equals what ``decode_grid`` (the function that sizes the
    kernel's grid on the device) makes of the ``t`` each dispatch was
    handed, a layer at a time; a stretch without a decode tick adds
    nothing, and a dense server, which runs no paged kernel, counts
    none."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    from paddle_tpu.ops.pallas.paged_attention import decode_grid
    pt.seed(23)
    model = GPTForCausalLM(gpt2_tiny())
    model.eval()
    kw = dict(max_slots=4, max_cache_len=CACHE, tick_block=tick_block)
    srv = ContinuousBatchingServer(model, cache_backend="paged",
                                   page_size=8, num_pages=33,
                                   telemetry=True, **kw)
    seen = _watch_dispatches(srv)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11)]
    rids = [srv.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (9, 20))]
    grid = lambda: (srv.stats["decode_grid_steps"],
                    srv.stats["decode_live_pages"])
    assert grid() == (0, 0)
    outs = srv.run()
    assert all(len(outs[r]) for r in rids)
    steps = live = 0
    for t, _ in seen:
        for j in range(tick_block):
            at = t + j
            pages, n = decode_grid(np.where(at < CACHE, at + 1, 0), 8)
            steps, live = steps + int(n), live + int(pages.sum())
    layers = model.cfg.num_layers
    assert grid() == (steps * layers, live * layers)
    assert steps >= live > 0
    samples = srv.telemetry.registry.snapshot()[
        "serving_decode_grid_total"]["samples"]
    assert (samples[("steps",)], samples[("live_pages",)]) == grid()
    done = grid()
    for _ in range(3):                     # nothing left to decode
        srv.step()
    assert grid() == done

    dense = ContinuousBatchingServer(model, **kw)
    dense.submit(prompts[0], max_new_tokens=4)
    dense.run()
    assert dense.stats["decode_ticks"] > 0
    assert (dense.stats["decode_grid_steps"],
            dense.stats["decode_live_pages"]) == (0, 0)


@pytest.mark.parametrize("budget", [None, 12])
def test_prefill_grid_counters_follow_the_dispatched_launches(budget):
    """``prefill_grid_steps`` / ``prefill_live_steps``: what the host
    adds a launch equals what ``prefill_grid`` (the function that sizes
    the kernel's grid on the device) makes of the ``t0`` and ``take``
    the launch was handed, a layer at a time — chunks carried over
    launches by a small budget included; a flat grid has no dead step
    but a launch's lone one, and a dense server, which runs no paged
    kernel, counts none."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
    from paddle_tpu.ops.pallas.paged_attention import prefill_grid
    from paddle_tpu.ops.pallas.ragged_prefill import QUERY_TILE
    pt.seed(23)
    model = GPTForCausalLM(gpt2_tiny())
    model.eval()
    kw = dict(max_slots=4, max_cache_len=CACHE)
    srv = ContinuousBatchingServer(model, cache_backend="paged",
                                   page_size=8, num_pages=33,
                                   telemetry=True,
                                   prefill_tokens_per_tick=budget, **kw)
    seen, real = [], srv._ragged_fn

    def spy(tokens, t0, caches, out_idx, take, slots):
        seen.append((np.asarray(t0), np.asarray(take), tokens.shape[1]))
        return real(tokens, t0, caches, out_idx, take, slots)

    srv._ragged_fn = spy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11, 29)]
    grid = lambda: (srv.stats["prefill_grid_steps"],
                    srv.stats["prefill_live_steps"])
    assert grid() == (0, 0)
    for p in prompts:
        srv.submit(p, max_new_tokens=3)
    srv.run()
    assert seen and (budget is None or len(seen) > 2)
    steps = live = 0
    for t0, take, width in seen:
        pages, n = prefill_grid(t0, take, width, QUERY_TILE, 8, CACHE // 8)
        steps, live = steps + int(n), live + int(pages.sum())
    layers = model.cfg.num_layers
    assert grid() == (steps * layers, live * layers)
    assert steps == live > 0               # every launch had work
    samples = srv.telemetry.registry.snapshot()[
        "serving_prefill_grid_total"]["samples"]
    assert (samples[("steps",)], samples[("live_steps",)]) == grid()

    dense = ContinuousBatchingServer(model, **kw)
    dense.submit(prompts[0], max_new_tokens=4)
    dense.run()
    assert dense.stats["prefill_tokens"] > 0
    assert (dense.stats["prefill_grid_steps"],
            dense.stats["prefill_live_steps"]) == (0, 0)
