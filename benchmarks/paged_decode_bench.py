"""Dense vs paged KV-cache continuous-batching decode (ISSUE 1).

Drives the same mixed-length workload — request budgets spanning
32..max_cache_len tokens in one slot pool — through
``ContinuousBatchingServer`` with ``cache_backend="dense"`` and
``"paged"`` and reports:

- decode throughput (generated tokens / wall-clock drain time),
- cache HBM: the dense backend allocates ``slots x max_cache_len`` rows
  up front; the paged pool is sized to the worst-case CONCURRENT token
  working set (sum of the largest ``max_slots`` request extents), so its
  footprint tracks actual tokens,
- decode-program compile count across slot churn (the block table is a
  runtime argument — it must stay at 1),
- token parity (the paged backend is bit-identical on the XLA path),
- steady-state GOODPUT ratio per mode (ISSUE 11: the goodput ledger's
  useful / total device tokens — the paged backend trades dense HBM
  for masked page DMAs the ledger makes visible),
- the FUSED serving tick (ISSUE 14, ``serving_mode="fused"``): one
  launch per tick over a live-page DMA schedule — tokens/s, goodput
  ratio (the acceptance bar: >= 10x the split paged ratio, because
  ``skipped_page_dma`` collapses to the schedule's ladder pad and
  ``null_redirect`` to zero), dispatches per tick, and the fused
  program's compiled FLOPs/HBM-bytes per token next to the split
  decode program's.

- the SHARDED paged column (ISSUE 16, ``--mesh``): the same paged
  workload served with the K/V pool sharded on the kv-head dim over a
  tensor-parallel mesh at mp in {1, 2, 4} — tokens/s, compiled decode
  HBM B/tok per shard, the measured per-device pool-byte fraction, and
  token parity vs the mp=1 run. On CPU the mesh pays real collective
  overhead per tick; the column is recorded honestly (capacity is the
  win — per-device pool bytes — not CPU throughput).

    python benchmarks/paged_decode_bench.py [--model tiny|350m]
        [--slots N] [--cache-len N] [--page-size N] [--track] [--mesh]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mixed_requests(rng, max_cache_len, n_requests):
    """Prompt/budget pairs whose total extents sweep 32..max_cache_len."""
    reqs = []
    total = 32
    for i in range(n_requests):
        prompt = int(rng.integers(8, 24))
        new = max(1, total - prompt)
        reqs.append((rng.integers(0, 256, (prompt,)).astype(np.int32),
                     new))
        total = min(total * 2, max_cache_len)
        if total == max_cache_len:
            total = 32 + int(rng.integers(0, 64))
    return reqs


def _warm_reqs(reqs, rng):
    """Same (prompt_len, budget) pairs — so the warm drain visits the
    same compile-geometry ladder points — but FRESH tokens, so the
    auto prefix cache stays cold for the timed drain."""
    return [(rng.integers(0, 256, (len(p),)).astype(np.int32), n)
            for p, n in reqs]


def _drain(srv, reqs, warm=None):
    if warm is not None:
        # untimed compile-warm pass: tokens/s below measures the
        # steady state, not XLA (the ladder compile counts are still
        # reported from the cost catalog)
        for p, n in warm:
            srv.submit(p, max_new_tokens=n)
        srv.run()
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=n) for p, n in reqs]
    outs = srv.run()
    dt = time.perf_counter() - t0
    toks = sum(len(outs[r]) for r in rids)
    return [outs[r] for r in rids], toks, dt


def main(model_name="tiny", slots=4, cache_len=1024, page_size=16,
         n_requests=12, track=False):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    from paddle_tpu.inference.kv_cache import PagedKVCache
    from paddle_tpu.models.llama import (LlamaForCausalLM, llama_350m,
                                         llama_tiny)
    from paddle_tpu.telemetry import CostCatalog, GoodputLedger

    pt.seed(7)
    cfg = (llama_tiny if model_name == "tiny" else llama_350m)(
        max_seq_len=max(cache_len, 128))
    model = LlamaForCausalLM(cfg)
    model.eval()
    L, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    itemsize = jnp.dtype(cfg.dtype).itemsize

    rng = np.random.default_rng(0)
    reqs = _mixed_requests(rng, cache_len, n_requests)
    warm = _warm_reqs(reqs, rng)
    extents = sorted((len(p) + n for p, n in reqs), reverse=True)
    # pool = worst-case concurrent working set (+1 null page, + one
    # page per slot of block-boundary slack)
    work_tokens = sum(extents[:slots])
    num_pages = -(-work_tokens // page_size) + slots + 1
    print(f"workload: {n_requests} requests, extents 32..{cache_len} "
          f"(peak concurrent {work_tokens} tokens), {slots} slots")

    led_d = GoodputLedger()
    dense = ContinuousBatchingServer(model, max_slots=slots,
                                     max_cache_len=cache_len,
                                     ledger=led_d)
    outs_d, toks_d, dt_d = _drain(dense, reqs, warm=warm)
    hbm_d = PagedKVCache.dense_hbm_bytes(slots, cache_len, L, kvh, hd,
                                         itemsize)
    good_d = led_d.snapshot()
    print(f"dense: {toks_d / dt_d:8,.0f} tok/s   "
          f"cache HBM {hbm_d / 2**20:8.2f} MiB "
          f"({slots} slots x {cache_len} rows)   "
          f"goodput {good_d['goodput_ratio']:.3f}")

    led_p = GoodputLedger()
    cat = CostCatalog()               # device-cost ledger (ISSUE 13)
    paged = ContinuousBatchingServer(model, max_slots=slots,
                                     max_cache_len=cache_len,
                                     cache_backend="paged",
                                     page_size=page_size,
                                     num_pages=num_pages,
                                     ledger=led_p, costs=cat)
    outs_p, toks_p, dt_p = _drain(paged, reqs, warm=warm)
    hbm_p = PagedKVCache.paged_hbm_bytes(num_pages, page_size, L, kvh,
                                         hd, itemsize)
    # the costed dispatch path runs the catalog's AOT executable
    # (priced once, cached on the server), so the jit cache is idle
    # and a decode shape leak can no longer recompile SILENTLY — it
    # would fail the dispatch loudly. compiles == 1 verifies decode
    # stayed one program; the catalog's post-warmup `recompiles`
    # counter (printed below) is the live churn signal for the
    # prefill chunk-width ladder
    compiles = cat.compiles().get("decode", 0)
    good_p = led_p.snapshot()
    print(f"paged: {toks_p / dt_p:8,.0f} tok/s   "
          f"cache HBM {hbm_p / 2**20:8.2f} MiB "
          f"({num_pages} pages x {page_size} rows, "
          f"{hbm_d / hbm_p:.1f}x smaller)   "
          f"goodput {good_p['goodput_ratio']:.3f}")
    waste_p = {k: v for k, v in sorted(good_p["tokens"].items())
               if k != "goodput"}
    print(f"paged waste breakdown (tokens): {waste_p}")
    print(f"decode compiles across slot churn: {compiles} (want 1)")
    # device-cost baseline (ISSUE 13): the compiled decode program's
    # own price per generated token — THE roofline numbers the fused
    # megakernel (ROADMAP item 2) must beat
    costs = cat.snapshot()
    dec = costs["ops"].get("decode", {"flops": 0.0, "hbm_bytes": 0.0,
                                      "dispatches": 0})
    # catalog totals span the warm + timed drains; per-token divides
    # by ALL generated tokens (no eos in this workload, so the warm
    # drain generated exactly its budgets)
    warm_toks = sum(n for _, n in warm)
    flops_tok = dec["flops"] / max(toks_p + warm_toks, 1)
    bytes_tok = dec["hbm_bytes"] / max(toks_p + warm_toks, 1)
    mfu = costs["mfu"]      # None off-chip: no DEVICE_PEAKS row to divide by
    util = "mfu/roofline not measured (device has no peaks row)" \
        if mfu is None else (f"mfu {mfu:.4f}  roofline "
                             f"{costs['roofline_ratio']:.4f}")
    print(f"device cost (compiled decode program): "
          f"{flops_tok:10,.0f} FLOPs/tok  {bytes_tok:10,.0f} HBM B/tok  "
          f"{util} (compiles {costs['compiles']}, "
          f"recompiles {costs['recompiles']})")
    parity = all(np.array_equal(a, b) for a, b in zip(outs_d, outs_p))
    print(f"token parity dense vs paged: {parity}")
    if hbm_d < 2 * hbm_p:
        print("WARNING: <2x HBM reduction — workload not mixed enough?")

    # ------------------------------------------------ fused serving tick
    led_f = GoodputLedger()
    cat_f = CostCatalog()
    fused = ContinuousBatchingServer(model, max_slots=slots,
                                     max_cache_len=cache_len,
                                     cache_backend="paged",
                                     page_size=page_size,
                                     num_pages=num_pages,
                                     serving_mode="fused",
                                     ledger=led_f, costs=cat_f)
    outs_f, toks_f, dt_f = _drain(fused, reqs, warm=warm)
    good_f = led_f.snapshot()
    print(f"fused: {toks_f / dt_f:8,.0f} tok/s   "
          f"cache HBM {hbm_p / 2**20:8.2f} MiB (same pool)   "
          f"goodput {good_f['goodput_ratio']:.3f}")
    waste_f = {k: v for k, v in sorted(good_f["tokens"].items())
               if k != "goodput"}
    print(f"fused waste breakdown (tokens): {waste_f}")
    disp_tick = fused.stats["tick_dispatches"]
    print(f"fused dispatches: {disp_tick} across warm + timed drains "
          f"(one per tick; split admission ticks add prefill + "
          f"state_push + block_table on top of decode)")
    costs_f = cat_f.snapshot()
    fop = costs_f["ops"].get("fused", {"flops": 0.0, "hbm_bytes": 0.0})
    print(f"device cost (compiled fused program):  "
          f"{fop['flops'] / max(toks_f + warm_toks, 1):10,.0f} "
          f"FLOPs/tok  "
          f"{fop['hbm_bytes'] / max(toks_f + warm_toks, 1):10,.0f} "
          f"HBM B/tok  (compiles {costs_f['compiles']} on the "
          f"geometry ladder, recompiles {costs_f['recompiles']})")
    ratio_gain = good_f["goodput_ratio"] / max(good_p["goodput_ratio"],
                                               1e-9)
    parity_f = all(np.array_equal(a, b) for a, b in zip(outs_d, outs_f))
    print(f"token parity dense vs fused: {parity_f}")
    fused_ok = parity_f and ratio_gain >= 10.0
    print(f"goodput gain fused/split: {ratio_gain:,.0f}x "
          f"({'OK' if ratio_gain >= 10.0 else 'REGRESSION'}; "
          f"ISSUE 14 acceptance bar is 10x)")
    if track:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_track", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "scripts", "bench_track.py"))
        bench_track = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench_track)
        note = (f"{model_name} model, {slots} slots, cache {cache_len},"
                f" pg {page_size}; compiled-program pricing")
        for metric, value, unit in (
                ("paged_decode_tokens_per_sec", toks_p / dt_p,
                 "tokens/s"),
                ("paged_decode_flops_per_token", flops_tok, "flops"),
                ("paged_decode_hbm_bytes_per_token", bytes_tok,
                 "bytes"),
                *([("paged_decode_mfu", mfu, "ratio")]
                  if mfu is not None else []),
                ("fused_decode_tokens_per_sec", toks_f / dt_f,
                 "tokens/s"),
                ("fused_paged_goodput_ratio", good_f["goodput_ratio"],
                 "ratio")):
            r = bench_track.append_round(
                {"metric": metric, "value": value, "unit": unit,
                 "note": note})
            print(f"tracked {r['metric']} = {r['value']}")
    return 0 if parity and fused_ok else 1


def _track_rounds(rows, note):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_track", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "bench_track.py"))
    bench_track = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_track)
    for metric, value, unit in rows:
        r = bench_track.append_round(
            {"metric": metric, "value": value, "unit": unit,
             "note": note})
        print(f"tracked {r['metric']} = {r['value']}")


def mesh_main(slots=4, cache_len=256, page_size=16, n_requests=8,
              track=False):
    """``--mesh``: the sharded paged serving column (ISSUE 16).

    Same mixed workload through a paged server at mp in {1, 2, 4} on a
    kv-head-divisible tiny llama (4 kv heads — llama_tiny's 2 would cap
    sharding at mp=2). The mp=1 run is the oracle: every mesh run must
    emit identical tokens. Reported per mp: compile-warmed tokens/s,
    the compiled decode program's HBM bytes per token PER SHARD
    (catalog global bytes / shard count), and the measured per-device
    pool bytes as a fraction of the mp=1 pool."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.telemetry import CostCatalog

    if len(jax.devices()) < 4:
        print(f"--mesh needs >= 4 devices, have {len(jax.devices())} "
              f"(run under XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8)")
        return 1
    from jax.sharding import Mesh

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=8, num_kv_heads=4,
                      intermediate_size=128,
                      max_seq_len=max(cache_len, 128))
    pt.seed(7)
    model = LlamaForCausalLM(cfg)
    model.eval()

    rng = np.random.default_rng(0)
    reqs = _mixed_requests(rng, cache_len, n_requests)
    warm = _warm_reqs(reqs, rng)
    warm_toks = sum(n for _, n in warm)
    extents = sorted((len(p) + n for p, n in reqs), reverse=True)
    work_tokens = sum(extents[:slots])
    num_pages = -(-work_tokens // page_size) + slots + 1
    print(f"sharded paged column: {n_requests} requests, extents "
          f"32..{cache_len}, {slots} slots, {num_pages} pages x "
          f"{page_size} rows, 4 kv heads")

    results = {}
    for mp in (1, 2, 4):
        cat = CostCatalog()
        mesh = None if mp == 1 else Mesh(np.array(jax.devices()[:mp]),
                                         ("mp",))
        srv = ContinuousBatchingServer(model, max_slots=slots,
                                       max_cache_len=cache_len,
                                       cache_backend="paged",
                                       page_size=page_size,
                                       num_pages=num_pages, mesh=mesh,
                                       costs=cat)
        outs, toks, dt = _drain(srv, reqs, warm=warm)
        shards = srv._pool_shards
        op = "decode" if shards <= 1 else f"decode_mp{shards}"
        dec = cat.snapshot()["ops"].get(op, {"hbm_bytes": 0.0})
        bytes_tok_shard = dec["hbm_bytes"] / max(toks + warm_toks, 1) \
            / max(shards, 1)
        shard_bytes = srv._shard_pool_bytes()
        results[mp] = dict(outs=outs, toks_s=toks / dt,
                           bytes_tok_shard=bytes_tok_shard,
                           shard_bytes=shard_bytes,
                           compiles=cat.compiles().get(op, 0),
                           recompiles=cat.recompiles)
        frac = shard_bytes / results[1]["shard_bytes"]
        # decode compiles == 1 is the steady-state gate: the sharded
        # decode signature is static across slot churn. The catalog's
        # `recompiles` counter also ticks on prefill chunk-width LADDER
        # DISCOVERY (a cold catalog warms on the first width, then
        # meets the next) — printed for honesty, not gated
        print(f"mp={mp}: {toks / dt:8,.0f} tok/s   "
              f"decode HBM/shard {bytes_tok_shard:10,.0f} B/tok   "
              f"pool bytes/device {shard_bytes / 2**20:6.2f} MiB "
              f"({frac:.3f}x of mp=1)   "
              f"decode compiles {results[mp]['compiles']} (ladder "
              f"recompiles {results[mp]['recompiles']})")

    parity = all(
        np.array_equal(a, b)
        for mp in (2, 4)
        for a, b in zip(results[1]["outs"], results[mp]["outs"]))
    frac4 = results[4]["shard_bytes"] / results[1]["shard_bytes"]
    print(f"token parity mp=2/mp=4 vs mp=1: {parity}")
    print(f"per-device pool bytes at mp=4: {frac4:.3f}x of mp=1 "
          f"(want <= 0.25 + block-boundary epsilon)")
    ok = parity and frac4 <= 0.3 \
        and all(r["compiles"] == 1 for r in results.values())
    if track:
        note = (f"tiny 4-kv-head llama, {slots} slots, cache "
                f"{cache_len}, pg {page_size}; CPU forced-host mesh — "
                f"collective overhead included, capacity (pool "
                f"bytes/device) is the win")
        _track_rounds(
            [(f"sharded_paged_decode_tokens_per_sec_mp{mp}",
              results[mp]["toks_s"], "tokens/s") for mp in (1, 2, 4)]
            + [("sharded_paged_decode_hbm_bytes_per_token_per_shard_mp4",
                results[4]["bytes_tok_shard"], "bytes"),
               ("sharded_paged_pool_bytes_frac_mp4", frac4, "ratio")],
            note)
    return 0 if ok else 1


if __name__ == "__main__":
    kw = {}
    argv = sys.argv[1:]
    if "--mesh" in argv:
        # the forced host-device env must land BEFORE jax initializes
        # (mesh_main imports jax lazily, so setting it here works).
        # ROADMAP C5: a CPU-mesh default behind a device-metric name;
        # goes when the mesh column becomes a cell on the chip (A8).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if "--model" in argv:
        kw["model_name"] = argv[argv.index("--model") + 1]
    if "--slots" in argv:
        kw["slots"] = int(argv[argv.index("--slots") + 1])
    if "--cache-len" in argv:
        kw["cache_len"] = int(argv[argv.index("--cache-len") + 1])
    if "--page-size" in argv:
        kw["page_size"] = int(argv[argv.index("--page-size") + 1])
    if "--track" in argv:             # append this round to BENCHLOG
        kw["track"] = True
    if "--mesh" in argv:
        kw.pop("model_name", None)
        sys.exit(mesh_main(**kw))
    sys.exit(main(**kw))
