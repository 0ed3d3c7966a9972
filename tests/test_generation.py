"""model.generate() (models/generation.py): the on-device cached decode
must reproduce the model's own eager forward run token-by-token — the
cache math (GQA, rope offsets, learned positions, tied head) is validated
against the full recompute-every-step loop."""
import numpy as np
import pytest

import paddle_tpu as pt


def _naive_greedy(model, ids_np, n_new):
    """Reference: full forward over the growing sequence each step."""
    ids = ids_np.copy()
    for _ in range(n_new):
        logits = model(pt.to_tensor(ids)).numpy()
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids


class TestGreedyParity:
    @pytest.mark.slow
    def test_llama_gqa_generate_matches_eager(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(11)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 256, (2, 5)).astype(np.int32)
        want = _naive_greedy(model, ids, 6)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got.numpy()), want)

    def test_gpt_generate_matches_eager(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(12)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        rng = np.random.default_rng(4)
        ids = rng.integers(0, model.cfg.vocab_size, (2, 4)).astype(np.int32)
        want = _naive_greedy(model, ids, 5)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(got.numpy()), want)

    @pytest.mark.slow
    def test_mixtral_generate_matches_eager(self):
        """MoE decode (dropless dense-expert top-2 combine) must equal
        the eager capacity-dispatch forward at under-capacity loads.
        (slow: two mixtral compiles; server-level mixtral parity stays
        tier-1 in test_continuous_batching/test_paged_attention.)"""
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        pt.seed(31)
        model = MixtralForCausalLM(mixtral_tiny())
        model.eval()
        rng = np.random.default_rng(5)
        ids = rng.integers(0, 256, (2, 4)).astype(np.int32)
        want = _naive_greedy(model, ids, 5)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(got.numpy()), want)

    def test_generate_repeated_call_reuses_programs(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(13)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        ids = np.arange(6, dtype=np.int32).reshape(2, 3)
        a = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                           max_cache_len=32)
        bundle1 = model._pt_decode_cache
        b = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                           max_cache_len=32)
        assert model._pt_decode_cache is bundle1, "bundle rebuilt"
        np.testing.assert_array_equal(a.numpy(), b.numpy())

    def test_gpt_cache_beyond_position_table_refused(self):
        """code-review r5: wpe gathers clamp silently past max_seq_len —
        the builder must refuse oversized caches instead."""
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        model = GPTForCausalLM(gpt2_tiny())
        ids = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError, match="position table"):
            model.generate(pt.to_tensor(ids), max_new_tokens=4,
                           max_cache_len=model.cfg.max_seq_len + 64)

    def test_generate_length_guard(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        model = LlamaForCausalLM(llama_tiny())
        ids = np.zeros((1, 10), np.int32)
        with pytest.raises(ValueError, match="max_cache_len"):
            model.generate(pt.to_tensor(ids), max_new_tokens=8,
                           max_cache_len=16)


class TestSampling:
    def test_topk1_equals_greedy(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(14)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        ids = np.arange(8, dtype=np.int32).reshape(2, 4)
        greedy = model.generate(pt.to_tensor(ids), max_new_tokens=5)
        sampled = model.generate(pt.to_tensor(ids), max_new_tokens=5,
                                 do_sample=True, top_k=1, seed=0)
        np.testing.assert_array_equal(greedy.numpy(), sampled.numpy())

    def test_same_seed_reproducible_different_seed_varies(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(15)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        ids = np.arange(4, dtype=np.int32).reshape(1, 4)
        kw = dict(max_new_tokens=12, do_sample=True, temperature=3.0)
        a = model.generate(pt.to_tensor(ids), seed=7, **kw)
        b = model.generate(pt.to_tensor(ids), seed=7, **kw)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        outs = [model.generate(pt.to_tensor(ids), seed=s, **kw).numpy()
                for s in range(8, 12)]
        assert any(not np.array_equal(a.numpy(), o) for o in outs), \
            "hot sampling produced identical sequences for 4 other seeds"

    def test_eos_pads_tail(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(16)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        ids = np.zeros((1, 3), np.int32)
        # run greedy once to learn the first generated token, then use it
        # as "eos": everything after the first new token must be eos
        first = model.generate(pt.to_tensor(ids), max_new_tokens=1)
        eos = int(first.numpy()[0, -1])
        out = model.generate(pt.to_tensor(ids), max_new_tokens=6,
                             eos_token_id=eos).numpy()[0]
        assert (out[3:] == eos).all()


class TestQwenVLGenerate:
    @pytest.mark.slow
    def test_vl_generate_matches_eager_joint_forward(self):
        """Multimodal decode: visual prefix in the cache, text decoding
        token-for-token equal to the full joint recompute. (slow: five
        full joint recomputes; text-only VL decode stays tier-1.)"""
        from paddle_tpu.models.qwen_vl import QwenVL, qwen_vl_tiny
        pt.seed(81)
        model = QwenVL(qwen_vl_tiny())
        model.eval()
        rng = np.random.default_rng(17)
        pixels = pt.to_tensor(
            rng.standard_normal((1, 3, 16, 16)).astype("float32"))
        ids = rng.integers(0, 256, (1, 4)).astype(np.int32)

        # naive loop: full joint forward each step, argmax last position
        cur = ids.copy()
        for _ in range(5):
            logits = model(pt.to_tensor(cur), pixels).numpy()
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)

        got = model.generate(pt.to_tensor(ids), pixels, max_new_tokens=5,
                             max_cache_len=64)
        np.testing.assert_array_equal(got.numpy(), cur)

    @pytest.mark.slow
    def test_vl_generate_text_only(self):
        """Without pixels it degrades to plain llama-style decode."""
        from paddle_tpu.models.qwen_vl import QwenVL, qwen_vl_tiny
        pt.seed(82)
        model = QwenVL(qwen_vl_tiny())
        model.eval()
        ids = np.arange(4, dtype=np.int32)[None]
        cur = ids.copy()
        for _ in range(4):
            logits = model(pt.to_tensor(cur)).numpy()
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                             max_cache_len=32)
        np.testing.assert_array_equal(got.numpy(), cur)


class TestChunkedPrefill:
    def test_chunked_prefill_matches_whole_prompt(self):
        """Fixed-size prefill chunks (prompt padded up): same tokens as
        the one-shot prefill — padded rows live above the frontier."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(61)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(11)
        ids = rng.integers(0, 256, (2, 7)).astype(np.int32)
        want = model.generate(pt.to_tensor(ids), max_new_tokens=5,
                              max_cache_len=64)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=5,
                             max_cache_len=64, prefill_chunk=3)
        np.testing.assert_array_equal(got.numpy(), want.numpy())

    def test_chunked_prefill_gpt_positions(self):
        """GPT learned positions must be offset per chunk."""
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(62)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        rng = np.random.default_rng(12)
        ids = rng.integers(0, model.cfg.vocab_size, (1, 5)).astype(
            np.int32)
        want = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                              max_cache_len=32)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                             max_cache_len=32, prefill_chunk=2)
        np.testing.assert_array_equal(got.numpy(), want.numpy())

    def test_chunk_headroom_guard(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        model = LlamaForCausalLM(llama_tiny())
        ids = np.zeros((1, 13), np.int32)   # pad-to-18 > cache 16
        with pytest.raises(ValueError, match="chunk headroom"):
            model.generate(pt.to_tensor(ids), max_new_tokens=3,
                           max_cache_len=16, prefill_chunk=6)

    def test_server_chunked_prefill_parity(self):
        from paddle_tpu.inference.continuous_batching import (
            ContinuousBatchingServer)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(63)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
                   for n in (5, 8)]
        srv = ContinuousBatchingServer(model, max_slots=2,
                                       max_cache_len=64,
                                       prefill_chunk=4)
        rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        outs = srv.run()
        for rid, p in zip(rids, prompts):
            want = model.generate(pt.to_tensor(p[None]),
                                  max_new_tokens=5,
                                  max_cache_len=64).numpy()[0, len(p):]
            np.testing.assert_array_equal(outs[rid], want)


class TestWeightOnlyInt8:
    def test_int8_decode_close_to_fp32(self):
        """Weight-only int8 decode: prefill logits within quantization
        tolerance of fp32, and generation runs end to end."""
        import jax.numpy as jnp

        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(41)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(6)
        ids = rng.integers(0, 256, (2, 5)).astype(np.int32)

        b32 = model._decode_bundle(64, None)
        b8 = model._decode_bundle(64, "int8")
        x0 = model._prefill_embed(jnp.asarray(ids), None)
        out32, _ = b32[2](x0, b32[0](2), jnp.int32(0))
        out8, _ = b8[2](x0, b8[0](2), jnp.int32(0))
        lg32 = np.asarray(b32[3](out32[:, -1:]))
        lg8 = np.asarray(b8[3](out8[:, -1:]))
        rel = (np.abs(lg8 - lg32).max()
               / (np.abs(lg32).max() + 1e-9))
        assert rel < 0.05, f"int8 drift too large: {rel}"

        out = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                             weight_dtype="int8", max_cache_len=64)
        assert out.numpy().shape == (2, 9)

    def test_int8_bundle_cached_separately(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(42)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        ids = np.zeros((1, 3), np.int32)
        a = model.generate(pt.to_tensor(ids), max_new_tokens=3,
                           max_cache_len=32)
        b = model.generate(pt.to_tensor(ids), max_new_tokens=3,
                           weight_dtype="int8", max_cache_len=32)
        c = model.generate(pt.to_tensor(ids), max_new_tokens=3,
                           max_cache_len=32)
        # fp32 results stable across the interleaved int8 call
        np.testing.assert_array_equal(a.numpy(), c.numpy())


class TestBf16Generate:
    @pytest.mark.skipif(
        tuple(int(x) for x in __import__("jax").__version__
              .split(".")[:2]) < (0, 5),
        reason="bf16 eager-vs-decode exact tokens hit a sub-ulp top-2 "
               "tie (gap 0.008 at the divergence step) that this older "
               "XLA CPU rounds the other way; f32 parity and all server "
               "parity suites still assert exact tokens")
    def test_bf16_model_generate_matches_bf16_eager(self):
        """The serving dtype on TPU is bf16: decode parity must hold
        against the model's own bf16 eager forward."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(45)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        model.astype("bfloat16")
        rng = np.random.default_rng(25)
        ids = rng.integers(0, 256, (1, 5)).astype(np.int32)
        want = _naive_greedy(model, ids, 5)
        got = model.generate(pt.to_tensor(ids), max_new_tokens=5,
                             max_cache_len=32)
        np.testing.assert_array_equal(np.asarray(got.numpy()), want)


class TestInt8KVCache:
    def test_int8_kv_close_to_fp_and_actually_int8(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(43)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        rng = np.random.default_rng(19)
        ids = rng.integers(0, 256, (2, 6)).astype(np.int32)

        bfp = model._decode_bundle(32)
        b8 = model._decode_bundle(32, cache_dtype="int8")
        caches8 = b8[0](2)
        assert caches8["k"].dtype == jnp.int8 and "ks" in caches8
        x0 = model._prefill_embed(jnp.asarray(ids), None)
        outf, _ = bfp[2](x0, bfp[0](2), jnp.int32(0))
        out8, _ = b8[2](x0, b8[0](2), jnp.int32(0))
        lf = np.asarray(bfp[3](outf[:, -1:]))
        l8 = np.asarray(b8[3](out8[:, -1:]))
        rel = np.abs(l8 - lf).max() / (np.abs(lf).max() + 1e-9)
        assert rel < 0.05, f"int8 KV drift too large: {rel}"

        out = model.generate(pt.to_tensor(ids), max_new_tokens=4,
                             max_cache_len=32, cache_dtype="int8")
        assert out.numpy().shape == (2, 10)

    def test_int8_kv_through_server_parity(self):
        from paddle_tpu.inference.continuous_batching import (
            ContinuousBatchingServer)
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        pt.seed(44)
        model = GPTForCausalLM(gpt2_tiny())
        model.eval()
        rng = np.random.default_rng(21)
        p = rng.integers(0, model.cfg.vocab_size, (5,)).astype(np.int32)
        want = model.generate(pt.to_tensor(p[None]), max_new_tokens=4,
                              max_cache_len=32,
                              cache_dtype="int8").numpy()[0, 5:]
        srv = ContinuousBatchingServer(model, max_slots=1,
                                       max_cache_len=32,
                                       cache_dtype="int8")
        rid = srv.submit(p, max_new_tokens=4)
        np.testing.assert_array_equal(srv.run()[rid], want)


def test_process_logits_filters():
    import jax.numpy as jnp

    from paddle_tpu.inference.decode_loop import process_logits
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
    k2 = np.asarray(process_logits(logits, top_k=2))
    assert k2[0, 1] == 3.0 and k2[0, 2] == 2.0
    assert k2[0, 0] < -1e20 and k2[0, 3] < -1e20
    # top_p tiny: only the argmax survives
    p = np.asarray(process_logits(logits, top_p=1e-6))
    assert p[0, 1] == 3.0 and (p[0, [0, 2, 3]] < -1e20).all()
    # temperature scales
    t = np.asarray(process_logits(logits, temperature=2.0))
    np.testing.assert_allclose(t[0], [0.5, 1.5, 1.0, -0.5])


def _tiny(family):
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_tiny
        return GPTForCausalLM(gpt2_tiny())
    if family == "llama":
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        return LlamaForCausalLM(llama_tiny())
    if family == "mixtral":
        from paddle_tpu.models.mixtral import (MixtralForCausalLM,
                                               mixtral_tiny)
        return MixtralForCausalLM(mixtral_tiny())
    from paddle_tpu.models.keye_vl import KeyeVL2ForCausalLM, keye_vl2_tiny
    return KeyeVL2ForCausalLM(keye_vl2_tiny())


@pytest.mark.parametrize("family", ["gpt", "llama", "mixtral", "keye"])
def test_paged_bundle_brings_the_ragged_entry(family):
    """A decode bundle has five elements dense and six paged, whatever
    the family, and the sixth is the program the device trace knows as
    ``jit_prefill_tick``: the server's default ``prefill_mode`` rests on
    it, and the benchmark refuses a server whose default is not
    ragged."""
    import jax.numpy as jnp
    pt.seed(7)
    model = _tiny(family)
    model.eval()
    assert len(model._decode_bundle(32)) == 5
    paged = model._decode_bundle(32, cache_backend="paged", page_size=8,
                                 num_pages=9)
    assert len(paged) == 6
    z = jnp.zeros((2,), jnp.int32)
    text = paged[5].lower(jnp.zeros((2, 4), jnp.int32), z, paged[0](2),
                          z, z, z).as_text()
    assert "module @jit_prefill_tick" in text
