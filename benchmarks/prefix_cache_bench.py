"""Automatic prefix caching + ragged prefill on the paged serving stack
(ISSUES 5 + 6).

Drives a shared-system-prompt workload — the canonical serving shape:
every request is ``system_prompt + short user tail`` — through
``ContinuousBatchingServer(cache_backend="paged")`` in three modes:

- ``auto off``   no prefix reuse, dense per-admission prefill,
- ``dense  on``  auto prefix cache + the PR-5 dense prefill path (every
  auto hit pays the page-gather -> dense-seed -> scatter detour),
- ``ragged on``  auto prefix cache + batched ragged prefill straight
  into pool pages (ISSUE 6, the paged default),
- ``fused  on``  auto prefix cache + the FUSED serving tick (ISSUE 14,
  ``serving_mode="fused"``): every admission tick is ONE launch —
  prefill chunks and decode rows together over a live-page DMA
  schedule — so TTFT sheds the split path's per-admission dispatch
  overhead,

and reports:

- steady-state auto hit rate: hits / (requests - expected cold misses).
  The warmup admissions are submitted together BEFORE any donation has
  happened, so each is a structurally-guaranteed miss (BENCHNOTES
  Round 7 recorded them as "4 misses" without the exclusion) — the raw
  rate is printed alongside,
- prefill tokens per mode and the tokens SAVED by page reuse (the
  counter-backed number that generalizes),
- admission-path DISPATCHES per admission (``prefill_dispatches`` /
  ``admissions``) — the ISSUE 6 acceptance signal: ragged must drop
  this vs the dense-on baseline,
- TTFT p50/p99 (measured at the first ``on_token`` callback) and the
  prefill wall-clock split (``prefill_wall_s``) per mode,
- cached/pinned/free page occupancy at drain, plus eviction churn when
  ``--num-pages`` squeezes the pool,
- drain wall time per mode (best of N reps, compiles warmed first;
  noise-prone on shared CI — trust the counters).

Then the MULTI-TURN SESSION workload (ISSUE 17): N users each serve a
distinct first turn, then every user RETURNS with a second turn that
extends their own history (turn-1 prompt + its generated tokens + a
fresh tail — only an extension of the donated prompt run can re-hit
its pages). The pool is squeezed so the first turns' donated pages
cannot all stay HBM-resident, and the same workload runs twice at
EQUAL device pool size: ``host_tier=None`` (evictions drop pages —
the pre-tier stack) vs ``HostTier()`` (evictions spill to host, the
returning turn restores). The bench self-asserts that the tiered run's
turn-2 hit tokens STRICTLY beat the HBM-only run's, that restores
actually happened (none corrupt), and that both runs' outputs are
bit-identical — the tier changes residency, never tokens.

    python benchmarks/prefix_cache_bench.py [--requests N]
        [--system-tokens N] [--tail-tokens N] [--new-tokens N]
        [--slots N] [--num-pages N] [--reps N] [--budget N]
        [--sessions N] [--session-tokens N] [--session-new N] [--track]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build_model():
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(21)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _prompts(args):
    rng = np.random.default_rng(0)
    system = rng.integers(0, 256, (args.system_tokens,)).astype(np.int32)
    return [np.concatenate(
        [system, rng.integers(0, 256, (args.tail_tokens,))
         .astype(np.int32)]) for _ in range(args.requests)]


def _drain(model, prompts, args, auto, prefill_mode,
           serving_mode="split"):
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    srv = ContinuousBatchingServer(
        model, max_slots=args.slots, max_cache_len=args.max_cache_len,
        cache_backend="paged", page_size=args.page_size,
        num_pages=args.num_pages, auto_prefix_cache=auto,
        prefill_mode=prefill_mode, serving_mode=serving_mode,
        prefill_tokens_per_tick=args.budget,
        telemetry=True)     # the phase boundary feeds prefill_wall_s
    for p in prompts[:args.slots]:                  # warm the compiles
        srv.submit(p, max_new_tokens=2)
    srv.run()
    for p in prompts[:2]:       # warm the HIT path's programs too (the
        srv.submit(p, max_new_tokens=2)   # remainder chunk geometry
    srv.run()                             # differs from the cold one)
    n_warm = min(args.requests, args.slots) + min(args.requests, 2)
    if serving_mode == "fused":
        # the fused (C, W, G) geometry ladder depends on the FULL
        # admission mix — one untimed full pass keeps ladder compiles
        # out of the timed reps' TTFT tail
        for p in prompts:
            srv.submit(p, max_new_tokens=args.new_tokens)
        srv.run()
        n_warm += args.requests
    best = float("inf")
    ttfts = []
    for _ in range(args.reps):
        first_seen = {}

        def on_token(rid, toks):
            if rid not in first_seen:
                first_seen[rid] = time.perf_counter()

        t0 = time.perf_counter()
        submits = {srv.submit(p, max_new_tokens=args.new_tokens,
                              on_token=on_token): time.perf_counter()
                   for p in prompts}
        outs = srv.run()
        best = min(best, time.perf_counter() - t0)
        assert all(r in outs for r in submits)
        ttfts += [first_seen[r] - t for r, t in submits.items()
                  if r in first_seen]
    return best, ttfts, srv, n_warm


def _session_bench(model, args, host_tier):
    """One pass of the multi-turn session workload. Serving config is
    pinned (1 slot, page 8, 7-page pool) so the two passes compare at
    EQUAL device memory and the pool genuinely cannot hold every
    user's history: 16-token turn-1 prompts donate 2 full pages each,
    so by the later users the earlier users' pages have been evicted
    — dropped when ``host_tier`` is None, spilled when it is on."""
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    srv = ContinuousBatchingServer(
        model, max_slots=1, max_cache_len=64, cache_backend="paged",
        page_size=8, num_pages=7, auto_prefix_cache=True,
        prefill_mode="ragged", host_tier=host_tier)
    rng = np.random.default_rng(1)
    users = [rng.integers(0, 256, (args.session_tokens,))
             .astype(np.int32) for _ in range(args.sessions)]
    outs1 = []
    for p in users:                         # turn 1: distinct histories
        rid = srv.submit(p, max_new_tokens=args.session_new)
        outs1.append(np.asarray(srv.run()[rid]))
    h_tok0 = srv.stats["prefix_auto_hit_tokens"]
    outs2 = []
    for p, o in zip(users, outs1):          # turn 2: extend OWN history
        ext = np.concatenate([p, o.astype(np.int32),
                              rng.integers(0, 256, (2,))
                              .astype(np.int32)])
        rid = srv.submit(ext, max_new_tokens=args.session_new)
        outs2.append(np.asarray(srv.run()[rid]))
    tier = srv.host_tier
    free, live, pinned, cached = srv.pool_balance()
    return {"hit_tokens": srv.stats["prefix_auto_hit_tokens"] - h_tok0,
            "outs": outs1 + outs2, "live": live,
            "spilled": tier.spilled_pages_total if tier else 0,
            "restored": tier.restored_pages_total if tier else 0,
            "corrupt": tier.restore_corrupt_total if tier else 0,
            "host_stats": tier.stats() if tier else None}


def _row(name, t_wall, ttfts, srv):
    s = srv.stats
    disp = s["prefill_dispatches"] / max(s["admissions"], 1)
    p50, p99 = (np.percentile(ttfts, 50) * 1e3,
                np.percentile(ttfts, 99) * 1e3) if ttfts else (0, 0)
    print(f"{name:10s}: prefill {s['prefill_tokens']:6d} tok, "
          f"{disp:5.2f} disp/admission, "
          f"prefill wall {s['prefill_wall_s'] * 1e3:7.1f} ms, "
          f"TTFT p50 {p50:6.1f} / p99 {p99:6.1f} ms, "
          f"drain best {t_wall * 1e3:7.1f} ms")
    return disp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--system-tokens", type=int, default=24)
    ap.add_argument("--tail-tokens", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-cache-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--budget", type=int, default=None,
                    help="prefill_tokens_per_tick (ragged mode)")
    ap.add_argument("--sessions", type=int, default=6,
                    help="returning users in the multi-turn workload")
    ap.add_argument("--session-tokens", type=int, default=16,
                    help="turn-1 prompt tokens per user (2 donated "
                         "pages at the pinned page size 8)")
    ap.add_argument("--session-new", type=int, default=4)
    ap.add_argument("--track", action="store_true",
                    help="append fused TTFT + tiered-session rounds "
                         "to BENCHLOG.jsonl")
    args = ap.parse_args()

    model = _build_model()
    prompts = _prompts(args)
    t_off, tt_off, off, _ = _drain(model, prompts, args, auto=False,
                                   prefill_mode="dense")
    t_dn, tt_dn, dense_on, w_dn = _drain(model, prompts, args,
                                         auto=True,
                                         prefill_mode="dense")
    t_rg, tt_rg, ragged, w_rg = _drain(model, prompts, args, auto=True,
                                       prefill_mode="ragged")
    t_fu, tt_fu, fused, w_fu = _drain(model, prompts, args, auto=True,
                                      prefill_mode="ragged",
                                      serving_mode="fused")

    # per-server admission counts incl. warmup (_drain returns how
    # many warmers it submitted; only the FIRST wave — submitted
    # before any donation — is structurally cold)
    warm = min(args.requests, args.slots)   # pre-donation => cold
    shared_run = args.system_tokens // args.page_size * args.page_size

    print(f"workload: {args.requests} requests x {args.reps} reps "
          f"(+{warm} warmup), system {args.system_tokens} tok "
          f"(shared page run {shared_run}), tail {args.tail_tokens}, "
          f"{args.new_tokens} new")
    _row("auto off", t_off, tt_off, off)
    d_dn = _row("dense  on", t_dn, tt_dn, dense_on)
    d_rg = _row("ragged on", t_rg, tt_rg, ragged)
    d_fu = _row("fused  on", t_fu, tt_fu, fused)

    ok = True
    for name, srv, n_warm in (("dense", dense_on, w_dn),
                              ("ragged", ragged, w_rg),
                              ("fused", fused, w_fu)):
        n_req = args.requests * args.reps + n_warm
        hits = srv.stats["prefix_auto_hits"]
        steady = hits / max(n_req - warm, 1)
        print(f"{name:6s} hit rate  : steady-state {hits}/{n_req - warm}"
              f" = {steady:.2f}  (raw {hits}/{n_req} = "
              f"{hits / n_req:.2f}; the {warm} warmup admissions are "
              f"structurally cold)")
        saved = off.stats["prefill_tokens"] - srv.stats["prefill_tokens"]
        print(f"{name:6s} saved     : {saved} prefill tokens "
              f"({saved / max(off.stats['prefill_tokens'], 1) * 100:.0f}"
              f"% of cold)")
        free, live, pinned, cached = srv.pool_balance()
        print(f"{name:6s} pool      : free {free}, live {live}, pinned "
              f"{pinned}, cached {cached} (evicted "
              f"{srv._prefix.evicted_pages_total}, donated "
              f"{srv._prefix.donated_pages_total})")
        ok = ok and steady >= 0.95 and saved > 0 and live == 0
    # ISSUE 6 acceptance: ragged kills the auto-hit dispatch detour
    print(f"dispatch ratio    : ragged {d_rg:.2f} vs dense-on {d_dn:.2f}"
          f" per admission ({'OK' if d_rg < d_dn else 'REGRESSION'}; "
          f"counters are the signal, CPU wall time is "
          f"dispatch-dominated)")
    ok = ok and d_rg < d_dn
    # ISSUE 14: the fused tick IS the admission dispatch — exactly one
    # launch carries each admission wave's chunks
    print(f"fused  dispatches : {d_fu:.2f} per admission "
          f"({'OK' if d_fu <= d_rg else 'REGRESSION'}; the launch "
          f"doubles as the decode tick)")
    ok = ok and d_fu <= d_rg

    # ISSUE 17: multi-turn sessions — N users return to their own
    # history under a pool too small to keep it all HBM-resident
    from paddle_tpu.inference.kv_tier import HostTier
    hbm = _session_bench(model, args, None)
    tiered = _session_bench(model, args, HostTier())
    ideal = args.sessions * (args.session_tokens // 8) * 8
    t_rate = tiered["hit_tokens"] / max(ideal, 1)
    h_rate = hbm["hit_tokens"] / max(ideal, 1)
    print(f"\nsessions ({args.sessions} users x 2 turns, 7-page pool "
          f"both runs):")
    print(f"hbm-only  turn 2  : {hbm['hit_tokens']:4d}/{ideal} hit "
          f"tokens ({h_rate:.2f}) — evictions DROPPED the history")
    hs = tiered["host_stats"]
    print(f"tiered    turn 2  : {tiered['hit_tokens']:4d}/{ideal} hit "
          f"tokens ({t_rate:.2f}), spilled "
          f"{tiered['spilled']} pages, restored {tiered['restored']}, "
          f"corrupt {tiered['corrupt']}; host now holds "
          f"{hs['entries']} pages / {hs['bytes_used']} bytes")
    sess_ok = (tiered["hit_tokens"] > hbm["hit_tokens"]
               and tiered["restored"] > 0 and tiered["corrupt"] == 0
               and tiered["live"] == 0 and hbm["live"] == 0
               and all(np.array_equal(a, b) for a, b
                       in zip(hbm["outs"], tiered["outs"])))
    print(f"session guard     : tiered strictly beats hbm-only at "
          f"equal device memory, outputs bit-identical "
          f"({'OK' if sess_ok else 'REGRESSION'})")
    ok = ok and sess_ok
    if args.track:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_track", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "scripts", "bench_track.py"))
        bench_track = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench_track)
        p50 = float(np.percentile(tt_fu, 50) * 1e3) if tt_fu else 0.0
        r = bench_track.append_round(
            {"metric": "fused_prefix_ttft_p50_ms", "value": p50,
             "unit": "ms",
             "note": f"{args.requests} reqs x {args.reps} reps, "
                     f"system {args.system_tokens} tok, CPU "
                     f"llama_tiny; serving_mode=fused"})
        print(f"tracked {r['metric']} = {r['value']:.1f}")
        r2 = bench_track.append_round(
            {"metric": "tiered_session_turn2_hit_rate", "value": t_rate,
             "unit": "ratio",
             "note": f"{args.sessions} users x 2 turns, 7-page pool, "
                     f"host tier on (hbm-only baseline {h_rate:.2f}); "
                     f"restored {tiered['restored']} pages"})
        print(f"tracked {r2['metric']} = {r2['value']:.2f}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
