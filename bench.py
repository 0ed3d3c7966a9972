"""Benchmark: flagship train-step throughput on the TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device"}. Model: GPT-2 345M causal-LM train step (BASELINE.json config
1), bf16 compute, jitted end-to-end (forward+backward+AdamW). MFU
accounting per BASELINE.md: 6*N*tokens/sec / peak bf16 FLOPs;
vs_baseline is the fraction of the 45%-MFU north star. Every mode fails
without a TPU; ``chip_smoke.py`` is the quickest proof the system starts
on the chip, and ROADMAP A0 reshapes these modes into benchmark cells.
"""
import json
import sys
import time

import numpy as np


def _require_tpu():
    """Every mode measures the chip: fail at once anywhere else (a
    CPU number under a device metric's name is worse than none), and
    place the compile cache before the first compile."""
    import jax
    from paddle_tpu.device import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). No measurement.")
    enable_compile_cache()


def emit(record):
    """Print the result line, naming the device it was measured on."""
    import jax
    dev = jax.devices()[0]
    print(json.dumps(dict(record, device={
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()})))


METRICS = {
    "gpt2": "gpt2_345m_train_tokens_per_sec_per_chip",
    "llama350m": "llama_350m_train_tokens_per_sec_per_chip",
    "moe": "mixtral_8e_top2_train_tokens_per_sec_per_chip",
    "llama1b3": "llama_1b3_train_tokens_per_sec_per_chip",
    "llama2b7": "llama_2b7_train_tokens_per_sec_per_chip",
    "decode": "gpt2_345m_decode_tokens_per_sec",
    "serve": "gpt2_345m_serve_tokens_per_sec",
}


def _build_model(config_name):
    """Returns (model, cfg, metric_name, batch, seq)."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt2_345m
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_350m

    if config_name == "llama350m":
        # BASELINE.md's llama family on the single bench chip: the 7B
        # TP(+sharding) configs need a multi-chip slice; this runs the
        # same architecture (RMSNorm/rope/SwiGLU/flash-attn path) sized
        # for one chip and reports the same tokens/s/chip metric.
        cfg = llama_350m()
        return (LlamaForCausalLM(cfg), cfg, METRICS["llama350m"], 8, 1024)
    if config_name == "moe":
        # BASELINE.md MoE row (DeepSeek-MoE / Mixtral family): top-2 of 8
        # SwiGLU experts, GShard grouped dispatch, aux loss in the step.
        from paddle_tpu.models.mixtral import MixtralForCausalLM, moe_350m_8e
        cfg = moe_350m_8e(moe_group_size=1024)
        return (MixtralForCausalLM(cfg), cfg, METRICS["moe"], 8, 1024)
    cfg = gpt2_345m(dropout=0.0)
    return (GPTForCausalLM(cfg), cfg, METRICS["gpt2"], 8, 1024)


def main_llama1b3(config_name="llama1b3"):
    """Largest-fits single-chip runs (VERDICT r5 #2).

    llama1b3: a 1.26B llama (TinyLlama-class: L=22, H=2048, F=5632,
    16 heads x 128) trained bf16 with per-block rematerialization,
    Pallas flash attention, and chunked fused linear+CE — HBM budget
    (16 GB): params 2.5 GB + grads 2.5 GB + bf16 Adam moments 5 GB +
    remat'd activations ~0.8 GB.

    llama2b7: the stretch point — ~2.7B (L=32, H=2560, F=6912, 20
    heads x 128) with an Adafactor-style factored second moment (+
    first-moment-free) update: params 5.4 GB + grads 5.4 GB + factored
    state ~15 MB + remat'd activations; the moment memory Adam would
    need (11 GB) does not fit beside them. The measured trend across
    345M -> 1.26B -> 2.7B is the evidence line toward the 7B row.

    The step builds from raw stacked arrays (no Layer objects) so
    device init is ONE jitted program.
    """
    import os
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.parallel.hybrid import _rope_tables_np
    from paddle_tpu.telemetry.costs import device_peaks

    big = config_name == "llama2b7"
    if big:
        L_, H_, F_, V_ = 32, 2560, 6912, 32000
        NH = 20
    else:
        L_, H_, F_, V_ = 22, 2048, 5632, 32000
        NH = 16
    opt = os.environ.get("PT_BENCH_2B_OPT",
                         "adafactor" if big else "adam")
    dims = os.environ.get("PT_BENCH_2B_DIMS")    # "L,H,F,V,NH" (smoke)
    if dims:
        L_, H_, F_, V_, NH = (int(x) for x in dims.split(","))
    HD = H_ // NH
    B = int(os.environ.get("PT_BENCH_2B_BATCH", "2" if big else "4"))
    S = int(os.environ.get("PT_BENCH_2B_SEQ", "2048"))
    fused = os.environ.get("PT_BENCH_2B_FUSED", "1") != "0"
    eps = 1e-5

    dev = jax.devices()[0]

    def init(key):
        ks = jax.random.split(key, 10)
        sd = 0.02

        def nrm(k, *shape):
            return (jax.random.normal(k, shape, jnp.float32) * sd
                    ).astype(jnp.bfloat16)

        return {
            "table": nrm(ks[0], V_, H_),
            "blocks": {
                "ln1": jnp.ones((L_, H_), jnp.bfloat16),
                "ln2": jnp.ones((L_, H_), jnp.bfloat16),
                "wq": nrm(ks[1], L_, H_, H_), "wk": nrm(ks[2], L_, H_, H_),
                "wv": nrm(ks[3], L_, H_, H_), "wo": nrm(ks[4], L_, H_, H_),
                "wg": nrm(ks[5], L_, H_, F_), "wu": nrm(ks[6], L_, H_, F_),
                "wd": nrm(ks[7], L_, F_, H_),
            },
            "norm": jnp.ones((H_,), jnp.bfloat16),
            "head": nrm(ks[8], H_, V_),
        }

    with jax.default_device(dev):
        params = jax.jit(init)(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda a: a.block_until_ready(), params)
        if opt == "adafactor":
            # factored second moment (Shazeer-Stern): row/col accumulators
            # over the trailing matrix dims — ~15 MB of state for 2.7B
            state = {
                "vr": jax.tree_util.tree_map(
                    lambda p: jnp.zeros(
                        p.shape[:-1] if p.ndim >= 2 else p.shape,
                        jnp.float32), params),
                "vc": jax.tree_util.tree_map(
                    lambda p: jnp.zeros(
                        p.shape[:-2] + p.shape[-1:] if p.ndim >= 2
                        else (1,), jnp.float32), params),
            }
        else:
            # bf16 moments: the 20-step bench measures throughput; fp32
            # moments (+5 GB) would not fit beside grads at this size
            state = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
                     "v": jax.tree_util.tree_map(jnp.zeros_like, params)}
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree_util.tree_leaves(params))

    cos_np, sin_np = _rope_tables_np(HD, S, 10000.0)
    cos = jnp.asarray(cos_np, jnp.bfloat16)
    sin = jnp.asarray(sin_np, jnp.bfloat16)

    def rms(x, w):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                       keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
                ).astype(x.dtype) * w

    def rope(t):
        # t [B, S, NH, HD]; tables [S, HD/2]
        t1, t2 = jnp.split(t, 2, axis=-1)
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        return jnp.concatenate([t1 * c - t2 * s, t1 * s + t2 * c], -1)

    def attn(q, k, v):
        return fa._flash(q.transpose(0, 2, 1, 3),
                         k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), 1.0 / np.sqrt(HD),
                         True).transpose(0, 2, 1, 3)

    def block(p, x):
        hn = rms(x, p["ln1"])
        q = rope((hn @ p["wq"]).reshape(B, S, NH, HD))
        k = rope((hn @ p["wk"]).reshape(B, S, NH, HD))
        v = (hn @ p["wv"]).reshape(B, S, NH, HD)
        x = x + attn(q, k, v).reshape(B, S, H_) @ p["wo"]
        hn = rms(x, p["ln2"])
        return x + (jax.nn.silu(hn @ p["wg"]) * (hn @ p["wu"])) @ p["wd"]

    def fwd(ps, ids):
        x = ps["table"][ids]

        def body(xx, blk):
            return block(blk, xx), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, ps["blocks"])
        h = rms(x, ps["norm"])
        if fused:
            return fused_linear_cross_entropy(
                h[:, :-1], ps["head"], ids[:, 1:], chunk_size=2046)
        lg = (h[:, :-1] @ ps["head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, -1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()

    b1, b2, lr, adam_eps = 0.9, 0.999, 1e-4, 1e-8

    def step(params, state, ids, i):
        loss, grads = jax.value_and_grad(fwd)(params, ids)

        is_tup = lambda t: isinstance(t, tuple)  # noqa: E731

        if opt == "adafactor":
            def upd(p, g, vr, vc):
                g2 = jnp.square(g.astype(jnp.float32)) + 1e-30
                if p.ndim >= 2:
                    vr2 = b2 * vr + (1 - b2) * g2.mean(-1)
                    vc2 = b2 * vc + (1 - b2) * g2.mean(-2)
                    vhat = (vr2[..., :, None] * vc2[..., None, :]
                            / (vr2.sum(-1, keepdims=True)[..., None]
                               + 1e-30))
                else:
                    vr2 = b2 * vr + (1 - b2) * g2
                    vc2 = vc
                    vhat = vr2
                vhat = vhat / (1 - jnp.power(b2, i))
                u = g.astype(jnp.float32) / jnp.sqrt(vhat + 1e-30)
                rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
                u = u / jnp.maximum(1.0, rms)     # update clipping d=1
                p2 = p.astype(jnp.float32) - lr * u
                return (p2.astype(p.dtype), vr2, vc2)

            out = jax.tree_util.tree_map(upd, params, grads,
                                         state["vr"], state["vc"])
            return (loss,
                    jax.tree_util.tree_map(lambda t: t[0], out,
                                           is_leaf=is_tup),
                    {"vr": jax.tree_util.tree_map(lambda t: t[1], out,
                                                  is_leaf=is_tup),
                     "vc": jax.tree_util.tree_map(lambda t: t[2], out,
                                                  is_leaf=is_tup)})

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m2 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v2 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            mhat = m2 / (1 - jnp.power(b1, i))
            vhat = v2 / (1 - jnp.power(b2, i))
            p2 = p.astype(jnp.float32) - lr * mhat / (jnp.sqrt(vhat)
                                                      + adam_eps)
            return (p2.astype(p.dtype), m2.astype(m.dtype),
                    v2.astype(v.dtype))

        out = jax.tree_util.tree_map(upd, params, grads, state["m"],
                                     state["v"])
        new_p = jax.tree_util.tree_map(lambda t: t[0], out,
                                       is_leaf=is_tup)
        new_m = jax.tree_util.tree_map(lambda t: t[1], out,
                                       is_leaf=is_tup)
        new_v = jax.tree_util.tree_map(lambda t: t[2], out,
                                       is_leaf=is_tup)
        return loss, new_p, {"m": new_m, "v": new_v}

    step = jax.jit(step, donate_argnums=(0, 1))

    ids = jax.device_put(np.random.randint(
        0, V_, size=(B, S)).astype(np.int32), dev)

    def fi(i):
        return jnp.asarray(i, jnp.float32)

    loss, params, state = step(params, state, ids, fi(1))
    float(loss)
    loss, params, state = step(params, state, ids, fi(2))
    float(loss)

    iters = 8
    t0 = time.perf_counter()
    for i in range(iters):
        loss, params, state = step(params, state, ids, fi(i + 3))
    final_loss = float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = B * S * iters / dt
    flops_per_token = 6 * n_params
    attn_flops = 12 * L_ * H_ * S      # causal-pair accounting per token
    mfu = tokens_per_sec * (flops_per_token + attn_flops) / device_peaks()[0]
    emit({
        "metric": METRICS[config_name],
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
    })
    print(f"  loss={final_loss:.4f} mfu={mfu:.3f} "
          f"params={n_params/1e6:.1f}M step_time={dt/iters*1000:.1f}ms "
          f"B={B} S={S} fused_ce={fused} opt={opt}", file=sys.stderr)


def main_decode():
    """Serving decode metric (VERDICT r5 #7): static-KV-cache
    autoregressive decode through incubate fused_multi_transformer at
    GPT-2 345M shapes — prefill 512 then 128 decode steps, batch 8 and
    batch 1. The JSON value is batch-8 SCAN-decode tokens/s: the whole
    decode loop runs on device as one lax.scan program
    (inference/decode_loop.py) so host dispatch is paid once per
    sequence — the per-step-dispatch loop is also measured for
    comparison. vs_baseline is the HBM-bandwidth utilization (decode is
    memory-bound: each step streams the 2-byte weights once), the
    roofline the reference's fused_multi_transformer_op.cu serving path
    also chases.
    """
    import jax
    import jax.numpy as jnp
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.telemetry.costs import device_peaks

    import os
    L, D, H, FF = 24, 1024, 16, 4096
    T_PRE, T_MAX, steps = 512, 1024, 128
    dims = os.environ.get("PT_BENCH_DEC_DIMS")   # "L,D,H,FF,TPRE,TMAX,steps"
    if dims:
        L, D, H, FF, T_PRE, T_MAX, steps = (int(x) for x in dims.split(","))
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16

    def mk(*s):
        return jnp.asarray(
            rng.standard_normal(s).astype("float32") * 0.02, dt)

    weights = dict(
        ln_scales=[jnp.ones((D,), dt) for _ in range(L)],
        ln_biases=[jnp.zeros((D,), dt) for _ in range(L)],
        qkv_weights=[mk(D, 3 * D) for _ in range(L)],
        qkv_biases=[jnp.zeros((3 * D,), dt) for _ in range(L)],
        linear_weights=[mk(D, D) for _ in range(L)],
        linear_biases=[jnp.zeros((D,), dt) for _ in range(L)],
        ffn_ln_scales=[jnp.ones((D,), dt) for _ in range(L)],
        ffn_ln_biases=[jnp.zeros((D,), dt) for _ in range(L)],
        ffn1_weights=[mk(D, FF) for _ in range(L)],
        ffn1_biases=[jnp.zeros((FF,), dt) for _ in range(L)],
        ffn2_weights=[mk(FF, D) for _ in range(L)],
        ffn2_biases=[jnp.zeros((D,), dt) for _ in range(L)],
    )
    n_params = sum(int(np.prod(w.shape)) for ws in weights.values()
                   for w in ws)

    def step_fn(x, caches, t, ws):
        out, new_caches = IF.fused_multi_transformer(
            x, num_heads=H, trans_qkvw=False, cache_kvs=caches,
            time_step=t, **ws)
        return out, new_caches

    jit_step = jax.jit(step_fn, donate_argnums=(1,))

    # scan decode: the WHOLE loop on device as one program (the
    # TPU-native serving design — host dispatch once per sequence, not
    # once per token; inference/decode_loop.py)
    from paddle_tpu.inference import scan_decode

    def bound_step(x, caches, t):
        return step_fn(x, caches, t, weights)

    results = {}
    scan_results = {}
    for B in (8, 1):
        caches = [jnp.zeros((2, B, H, T_MAX, D // H), dt)
                  for _ in range(L)]
        x_pre = mk(B, T_PRE, D)
        x_dec = mk(B, 1, D)
        t0 = time.perf_counter()
        out, caches = jit_step(x_pre, caches, jnp.int32(0), weights)
        float(out.sum())
        prefill_s = time.perf_counter() - t0
        out, caches = jit_step(x_dec, caches, jnp.int32(T_PRE), weights)
        float(out.sum())
        t0 = time.perf_counter()
        for i in range(1, steps):
            out, caches = jit_step(x_dec, caches,
                                   jnp.int32(T_PRE + i), weights)
        float(out.sum())
        dt_dec = time.perf_counter() - t0
        results[B] = (B * (steps - 1) / dt_dec, prefill_s)

        # scan variant over fresh caches (donate=False: reuse below).
        # Warmup MUST use the same `steps` as the timed call — the scan
        # length is part of the compiled program.
        caches2 = [jnp.zeros((2, B, H, T_MAX, D // H), dt)
                   for _ in range(L)]
        _, caches2 = jit_step(x_pre, caches2, jnp.int32(0), weights)
        out, _ = scan_decode(bound_step, x_dec, caches2, T_PRE, steps,
                             donate=False)         # warmup/compile
        float(np.asarray(out).sum())
        t0 = time.perf_counter()
        out, _ = scan_decode(bound_step, x_dec, caches2, T_PRE, steps,
                             donate=False)
        float(np.asarray(out).sum())
        dt_scan = time.perf_counter() - t0
        scan_results[B] = B * steps / dt_scan

    toks8 = scan_results[8]
    # weights stream once per STEP (B tokens): steps/s x bytes / BW
    bw_util = (toks8 / 8) * 2.0 * n_params / device_peaks()[1]
    emit({
        "metric": METRICS["decode"],
        "value": round(toks8, 1),
        "unit": "tokens/s",
        "vs_baseline": round(bw_util, 4),
    })
    print(f"  scan decode B=8: {toks8:,.0f} tok/s | B=1: "
          f"{scan_results[1]:,.0f} tok/s || per-step-dispatch B=8: "
          f"{results[8][0]:,.0f} tok/s (prefill+compile "
          f"{results[8][1]:.2f}s) | B=1: {results[1][0]:,.0f} tok/s "
          f"| params {n_params/1e6:.0f}M "
          f"| HBM util {bw_util:.2f}", file=sys.stderr)


def main_serve():
    """Continuous-batching server throughput (VERDICT r5 #7 follow-on):
    GPT-2 345M through inference.ContinuousBatchingServer — 16 requests
    (prompt 256, 128 new tokens each) over 8 slots, chunked prefill,
    tick_block=16 so each host dispatch runs 16 batched decode steps on
    device. Value = generated tokens/s; vs_baseline = HBM-bandwidth
    utilization of the decode phase (weights stream once per step for
    the whole slot batch).
    """
    import os

    from paddle_tpu.core.tensor import unwrap
    from paddle_tpu.inference import ContinuousBatchingServer
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, gpt2_345m
    from paddle_tpu.telemetry.costs import device_peaks

    dims = os.environ.get("PT_BENCH_SERVE_DIMS")   # "H,L,NH,V" smoke
    slots = int(os.environ.get("PT_BENCH_SERVE_SLOTS", "8"))
    n_req = int(os.environ.get("PT_BENCH_SERVE_REQS", "16"))
    t_pre = int(os.environ.get("PT_BENCH_SERVE_PROMPT", "256"))
    t_new = int(os.environ.get("PT_BENCH_SERVE_NEW", "128"))
    tick = int(os.environ.get("PT_BENCH_SERVE_TICK", "16"))

    if dims:
        H, L, NH, V = (int(x) for x in dims.split(","))
        cfg = GPTConfig(vocab_size=V, hidden_size=H, num_layers=L,
                        num_heads=NH, max_seq_len=t_pre + t_new)
    else:
        cfg = gpt2_345m(dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    model.astype("bfloat16")
    n_params = sum(int(np.prod(unwrap(prm).shape))
                   for _, prm in model.named_parameters())

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (t_pre,)).astype(np.int32)
               for _ in range(n_req)]
    max_cache = min(cfg.max_seq_len, t_pre + t_new)

    srv = ContinuousBatchingServer(
        model, max_slots=slots, max_cache_len=max_cache,
        prefill_chunk=t_pre, tick_block=tick)

    def run_batch():
        for p in prompts:
            srv.submit(p, max_new_tokens=t_new)
        t0 = time.perf_counter()
        outs = srv.run()
        dt = time.perf_counter() - t0
        total = sum(len(v) for v in outs.values())
        return total, dt

    run_batch()                    # warmup/compile (same server: the
    total, dt = run_batch()        # timed run reuses every program)
    toks = total / dt
    bw_util = (toks / slots) * 2.0 * n_params / device_peaks()[1]
    emit({
        "metric": METRICS["serve"],
        "value": round(toks, 1),
        "unit": "tokens/s",
        "vs_baseline": round(bw_util, 4),
    })
    print(f"  serve: {n_req} reqs x {t_new} new @ prompt {t_pre}, "
          f"{slots} slots, tick_block={tick}: {toks:,.0f} tok/s "
          f"({dt:.2f}s) | params {n_params/1e6:.0f}M | HBM util "
          f"{bw_util:.2f}", file=sys.stderr)


def main(config_name="gpt2"):
    _require_tpu()
    if config_name in ("llama1b3", "llama2b7"):
        return main_llama1b3(config_name)
    if config_name == "decode":
        return main_decode()
    if config_name == "serve":
        return main_serve()

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.jit import functional_call
    from paddle_tpu.telemetry.costs import device_peaks

    model, cfg, metric, batch, seq = _build_model(config_name)
    model.astype("bfloat16")
    model.eval()  # dropout off; still training math
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    init_fn, update_fn = opt.functional()
    params = model.raw_params()
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    state = init_fn(params)
    # master fp32 moments for stability (cheap on HBM at 345M)
    state = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), state)

    def loss_fn(logits, labels):
        lg = logits[:, :-1]
        lb = labels[:, 1:]
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, lb[..., None], -1).mean()

    is_moe = config_name == "moe"
    # fused chunked linear+CE (ops/fused_ce.py): avoids materializing the
    # [B,S,V] fp32 logits; enabled for the dense LM configs
    import os as _os
    # default off until A/B-measured on the real chip (flip after
    # benchmarks/fused_ce_bench.py shows a win)
    fused_ce = (config_name in ("gpt2", "llama350m")
                and _os.environ.get("PT_BENCH_FUSED_CE", "0") != "0")

    def step(params, state, ids, i):
        def compute(ps):
            if fused_ce:
                from paddle_tpu.ops.fused_ce import (
                    fused_linear_cross_entropy)
                hidden = functional_call(model, ps, ids, return_hidden=True)
                w = (ps["lm_head_weight"].T if config_name == "gpt2"
                     else ps["lm_head.weight"])
                return fused_linear_cross_entropy(
                    hidden[:, :-1], w, ids[:, 1:], chunk_size=2046)
            logits = functional_call(model, ps, ids)
            l = loss_fn(logits, ids)
            if is_moe:
                from paddle_tpu.core.tensor import unwrap
                aux = model.collect_aux_loss()
                if aux is not None:
                    l = l + cfg.aux_loss_coef * unwrap(aux)
            return l

        loss, grads = jax.value_and_grad(compute)(params)
        new_p, new_s = update_fn(grads, params, state, step=i)
        return loss, new_p, new_s

    step = jax.jit(step, donate_argnums=(0, 1))

    ids = np.random.randint(0, cfg.vocab_size, size=(batch, seq)).astype(
        np.int32)
    ids = jnp.asarray(ids)

    # warmup / compile (float() forces a host fetch: the step is done)
    loss, params, state = step(params, state, ids, 1)
    float(loss)
    loss, params, state = step(params, state, ids, 2)
    float(loss)

    # PT_BENCH_TRACE=<dir>: capture a jax.profiler trace of the steady
    # state (VERDICT r5 #8 — profiler-verified step: inspect for host
    # syncs / gaps between device kernels in the timed window)
    import contextlib
    trace_dir = _os.environ.get("PT_BENCH_TRACE")
    trace_cm = (jax.profiler.trace(trace_dir) if trace_dir
                else contextlib.nullcontext())

    iters = 10
    with trace_cm:
        t0 = time.perf_counter()
        for i in range(iters):
            loss, params, state = step(params, state, ids, i + 3)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
    if trace_dir:
        print(f"  profiler trace written to {trace_dir}", file=sys.stderr)

    tokens_per_sec = batch * seq * iters / dt
    n_active = n_params
    if is_moe:
        # MoE MFU counts ACTIVE params per token (top_k of num_experts);
        # capacity padding/drops are overhead, not useful FLOPs.
        exp = sum(int(np.prod(v.shape)) for k, v in params.items()
                  if ".experts." in k)
        n_active = n_params - exp + exp * cfg.top_k / cfg.num_experts
    flops_per_token = 6 * n_active
    # causal attention flops: 12 * L * S^2 * H per token pair accounting
    attn_flops = 12 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_sec * (flops_per_token + attn_flops) / device_peaks()[0]

    emit({
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
    })
    print(f"  loss={final_loss:.4f} mfu={mfu:.3f} "
          f"params={n_params/1e6:.1f}M step_time={dt/iters*1000:.1f}ms",
          file=sys.stderr)


if __name__ == "__main__":
    _argv = sys.argv[1:]
    _cfg = "gpt2"
    for _name in ("llama350m", "moe", "llama1b3", "llama2b7", "decode",
                  "serve"):
        if f"--config={_name}" in _argv or _name in _argv:
            _cfg = _name
    main(_cfg)
