"""Drain-with-migration vs evacuate+replay (ISSUE 18): what live
KV-page migration buys at the router's drain path.

Before this PR, draining a replica evacuated its queue and REPLAYED
mid-decode requests from token 0 on a sibling: the sibling re-prefills
the whole prompt and re-decodes every already-emitted token before the
stream advances (deterministic, bit-exact — but pure waste). With live
migration the drain hands off the written pool pages plus resolved
sampler state, and the sibling continues mid-chain: ZERO re-prefill,
zero re-decoded tokens.

This bench drives the SAME seeded workload through both drain modes at
the same fleet geometry and reports, per mode:

- drain-to-last-token wall (StubModel replicas: host scheduling cost,
  not FLOPs),
- the sibling's prefill-token delta across the drain (the re-prefill
  bill; the migration mode SELF-ASSERTS this is exactly 0),
- re-decoded (replayed) tokens — already-emitted tokens the sibling
  must re-decode before producing anything new (evacuate) vs none
  (migrate),
- pages handed off over the migration path,
- pool balance after the dust settles (leak check: live == 0 on both
  replicas, both modes).

Every completed stream is verified bit-exact against the StubModel
closed-form oracle, so a mode that cheated correctness would fail
before it reported a number.

    python benchmarks/migration_bench.py [--requests N] [--slots N]
        [--prompt-tokens N] [--new-tokens N] [--track]
"""
import argparse
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "scripts"))


def _servers(args):
    from _remote_stub import make_stub_server
    kw = dict(max_slots=args.slots, max_cache_len=args.max_cache_len,
              page_size=args.page_size, num_pages=args.pool_pages)
    return make_stub_server(**kw), make_stub_server(**kw)


def _run_mode(args, mode):
    """One drain drill: submit everything to the source replica, let
    every request stream mid-decode, then drain the source via
    ``mode`` — 'migrate' hands each slot's pages + sampler state to
    the sibling (``migrate_out``/``migrate_in``/``migrate_finish``);
    'evacuate' is the pre-migration story for a replica that must go
    away NOW: drop the slot and replay the request from token 0 on the
    sibling (same resolved seed, so the chain is bit-identical — at
    the price of a full re-prefill plus re-decoding every token the
    source had already emitted). Returns the counters."""
    from _serving_stub import stub_tokens
    from paddle_tpu.reliability import MigrationError

    # both replicas are driven by manual step() from this thread: the
    # drain then lands at an EXACT decode depth, every run — no serve
    # threads racing the gather, no flaky counters
    src, tgt = _servers(args)
    streamed = {}

    def sink(i):
        def cb(_r, toks):
            streamed[i] = streamed.get(i, 0) + len(toks)
        return cb

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 16,
                            (args.prompt_tokens,)).astype(np.int32)
               for _ in range(args.requests)]
    try:
        rids = [src.submit(p, max_new_tokens=args.new_tokens,
                           seed=100 + i, on_token=sink(i))
                for i, p in enumerate(prompts)]
        # decode every request to an exact mid-stream depth
        drain_at = args.new_tokens // 3
        for _ in range(10_000):
            if all(streamed.get(i, 0) >= drain_at
                   for i in range(args.requests)):
                break
            src.step()
        else:
            raise AssertionError("never reached mid-decode")
        emitted_at_drain = {}
        with src._lock:
            for st in src._slots:
                if st is not None:
                    emitted_at_drain[st.rid] = len(st.emitted)
        pre_prefill = tgt.stats["prefill_tokens"]
        moved = 0
        replayed = 0
        pages = 0
        carried = {}            # submission index -> rid on the sibling
        t0 = time.perf_counter()
        for i, rid in enumerate(rids):
            if mode == "migrate":
                try:
                    state, payloads = src.migrate_out(rid)
                except MigrationError:
                    continue     # finished at home while its siblings
                #                  were being gathered: nothing to move
                if str(state.get("phase")) == "prefill":
                    # mid-prefill slots became migratable with the
                    # prefill->decode handoff (ISSUE 20); this bench
                    # prices mid-DECODE drains only, so resume it at
                    # home rather than skewing the replay accounting
                    src.migrate_abort(rid)
                    print(f"  note: request {i} still mid-prefill at "
                          f"the drain point; skipped")
                    continue
                carried[i] = tgt.migrate_in(state, payloads,
                                            on_token=sink(i))
                src.migrate_finish(rid)
                pages += len(payloads)
            else:
                if not src.cancel(rid):
                    continue     # finished at home before the drain
                #                  reached it
                replayed += emitted_at_drain.get(rid, 0)
                carried[i] = tgt.submit(
                    prompts[i], max_new_tokens=args.new_tokens,
                    seed=100 + i, on_token=sink(i))
            moved += 1
        for _ in range(100_000):
            if tgt.in_flight() == 0 and not tgt._queue:
                break
            tgt.step()
        else:
            raise AssertionError("sibling never drained")
        results = {i: tgt.wait(r, timeout=5)
                   for i, r in carried.items()}
        wall = time.perf_counter() - t0
        # bit-exact against the oracle — seeds were fixed at submit,
        # so both drain modes must land the identical stream
        for i, out in results.items():
            np.testing.assert_array_equal(
                out, stub_tokens(prompts[i], args.new_tokens))
        reprefill = tgt.stats["prefill_tokens"] - pre_prefill
        assert moved == args.requests, \
            f"drain caught too few mid-decode: {moved}/{args.requests}"
        if mode == "migrate":
            # the acceptance contract, asserted on every run: a drain
            # that migrates pays ZERO re-prefill on the sibling
            assert reprefill == 0, \
                f"migration re-prefilled {reprefill} tokens"
            assert tgt.stats["admissions"] == 0
            assert tgt.stats["migrated_in"] == moved
            assert src.stats["migrations"] == moved
        for s, name in ((src, "src"), (tgt, "tgt")):
            bal = s.pool_balance()
            assert bal[1] == 0, f"{mode}/{name} leaked: {tuple(bal)}"
        return {"mode": mode, "moved": moved, "wall_s": wall,
                "reprefill_tokens": int(reprefill),
                "replayed_tokens": int(replayed),
                "pages_migrated": int(pages)}
    finally:
        src.stop()
        tgt.stop()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-tokens", type=int, default=11)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-cache-len", type=int, default=64)
    ap.add_argument("--pool-pages", type=int, default=64)
    ap.add_argument("--track", action="store_true",
                    help="append migration drain rounds to "
                         "BENCHLOG.jsonl")
    args = ap.parse_args(argv)
    if args.prompt_tokens + args.new_tokens > args.max_cache_len:
        ap.error("prompt + budget must fit max_cache_len")

    modes = [_run_mode(args, "migrate"), _run_mode(args, "evacuate")]
    by = {m["mode"]: m for m in modes}
    avoided = by["evacuate"]["reprefill_tokens"] \
        + by["evacuate"]["replayed_tokens"]

    print(f"\nmigration bench: {args.requests} requests, prompt "
          f"{args.prompt_tokens} + budget {args.new_tokens}, "
          f"2 replicas x {args.slots} slots, drain replica 0 "
          f"mid-decode")
    hdr = (f"{'drain mode':<10} {'moved':>6} {'wall ms':>8} "
           f"{'re-prefill tok':>15} {'re-decoded tok':>15} "
           f"{'pages moved':>12}")
    print(hdr)
    print("-" * len(hdr))
    for m in modes:
        print(f"{m['mode']:<10} {m['moved']:>6} "
              f"{m['wall_s'] * 1e3:>8.1f} "
              f"{m['reprefill_tokens']:>15} "
              f"{m['replayed_tokens']:>15} {m['pages_migrated']:>12}")
    print(f"wasted work avoided by migrating: {avoided} tokens "
          f"(re-prefill + replay the evacuate drain pays)")

    if args.track:
        import bench_track
        r = bench_track.append_round(
            {"metric": "migration_drain_target_prefill_tokens",
             "value": by["migrate"]["reprefill_tokens"],
             "unit": "tokens",
             "note": f"{by['migrate']['moved']} mid-decode requests "
                     f"migrated on drain, "
                     f"{by['migrate']['pages_migrated']} pages handed "
                     f"off; the migration path must keep this at "
                     f"exactly 0"})
        print(f"tracked {r['metric']} = {r['value']}")
        r2 = bench_track.append_round(
            {"metric": "migration_drain_replay_tokens_avoided",
             "value": avoided, "unit": "tokens",
             "note": f"re-prefill + re-decode the evacuate+replay "
                     f"drain paid for {by['evacuate']['moved']} "
                     f"mid-decode requests at the same geometry"})
        print(f"tracked {r2['metric']} = {r2['value']}")
    return {"modes": modes, "avoided": avoided}


if __name__ == "__main__":
    main()
