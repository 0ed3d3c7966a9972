"""What a serving driver (``open_loop``) is made of: the server built from
the mix's ``server`` block, the warm-up of this cell's shapes, the window with
its load generator, and the comparison with the reference.

From the program this takes ``ContinuousBatchingServer`` through its public
calls (``submit``, ``run``, ``start``, ``stop``, ``stats``), its telemetry
(``ServerTelemetry``: the queue spans and the registry's counters) and the cost
catalog's list of compiled programs (for their temp memory)."""
import gc
import threading
import time

import numpy as np

from perfbench import traffic
from perfbench.harness import say

# chip_smoke.py's check with tighter limits. bf16 through the layers moves a
# logit by a few hundredths of the logit spread, so an emitted token's f32
# reference logit must sit within LOGIT_MARGIN_STD reference-logit stds of
# that position's maximum, and ARGMAX_AGREE_MIN of the sample's tokens must be
# the reference's own argmax. Both come from what the v5e gave (PERF.md
# section 6: worst gap 0.080 std over all runs, a run's agreement 0.961 to
# 0.983): about twice the worst gap and two points under the least
# agreement, so that a path of lower precision than bf16 (an 8-bit cache or
# weights, whose rounding is 32 times coarser) fails one of them.
LOGIT_MARGIN_STD = 0.15
ARGMAX_AGREE_MIN = 0.94
CHECKED_REQUESTS = 16
TRACE_SLICE_S = 3.0


# ------------------------------------------------------------------ set-up
def build_server(ctx, model):
    from paddle_tpu.inference import ContinuousBatchingServer
    from paddle_tpu.telemetry import CostCatalog
    kw = dict(ctx.mix["server"])
    ctx.costs = CostCatalog()
    srv = ContinuousBatchingServer(
        model, cache_backend="paged", costs=ctx.costs,
        telemetry=True if ctx.trace else None, **kw)
    if srv.prefill_mode != "ragged":
        raise RuntimeError("ragged prefill is not the server's default")
    c = ctx.family.sizes(ctx.config, ctx.rehearse)
    pages = pool_pages(kw)
    pool = 2 * c["n_layer"] * pages * kw["page_size"] * c["n_embd"] * 2
    say(f"server: {kw}; K and V pool of {pages} pages, "
        f"{pool / 2**30:.3f} GiB in bf16")
    return srv


def pool_pages(server):
    """Pages of the pool, the null page included: the mix's own number, or
    the server's default (every slot at ``max_cache_len``)."""
    return server.get("num_pages") or (
        server["max_slots"] * server["max_cache_len"] // server["page_size"]
        + 1)


def warm_shapes(mix):
    """(prefill chunk widths, wave sizes) this mix can reach. A launch's
    width is the power of two over the longest take in it, and a take is
    anything from 1 to min(longest prompt, per-tick budget). A wave is the
    number of slots whose state is pushed in one tick: the budget bounds how
    many prompts FINISH in one launch, but one admission pass can RESERVE
    every slot, so waves go up to the slots."""
    server = mix["server"]
    budget = server.get("prefill_tokens_per_tick", server["max_cache_len"])
    longest = min(traffic.bounds(mix["prompt_tokens"])[1], budget)
    top = max(2, 1 << (longest - 1).bit_length())
    widths = [w for w in (1 << i for i in range(1, 12)) if w <= top]
    return widths, list(range(1, server["max_slots"] + 1))


def warm_up(srv, mix, vocab, seed):
    """Drive every shape once through the public, synchronous path: submit,
    then ``run()`` to the end. One prompt of each width, then ``k`` short
    prompts at once for each wave size (they activate in one launch, which
    is what sizes the slot-state scatters). Fresh tokens, so the prefix
    cache never shortens a prompt."""
    rng = np.random.default_rng([int(seed), 7])
    widths, waves = warm_shapes(mix)
    budget = mix["server"].get("prefill_tokens_per_tick",
                               mix["server"]["max_cache_len"])
    short = max(2, min(16, budget // max(waves)))
    # a prompt of w tokens launches at width w; the widest may have to leave
    # room for its two new tokens and still rounds up to the same width
    room = mix["server"]["max_cache_len"] - 2
    t0 = time.perf_counter()
    for kind, plan in (("prefill widths", [[min(w, room)] for w in widths]),
                       ("activation waves", [[short] * k for k in waves])):
        for lens in plan:
            for n in lens:
                srv.submit(rng.integers(0, vocab, n).astype(np.int32),
                           max_new_tokens=2)
            srv.run()
        t1 = time.perf_counter()
        say(f"  warm-up, {kind} ({len(plan)} of them, each with a decode "
            f"tick): {t1 - t0:.2f}s")
        t0 = t1
    return widths, waves


# ------------------------------------------------------------------ window
class Client:
    """The client's side of every request of a window: when it was due, when
    it was sent, and when each token arrived (``on_token``'s clock)."""

    def __init__(self, schedule):
        self.schedule = schedule
        self.rows = [{"due": None, "sent": None, "accepted": None, "rid": None,
                      "token_times": [], "tokens": [],
                      "prompt_tokens": len(r["prompt"]),
                      "max_new_tokens": r["max_new_tokens"],
                      "failed": False, "error": None}
                     for r in schedule]

    def on_token(self, row):
        times, toks = row["token_times"], row["tokens"]

        def cb(rid, chunk):
            now = time.perf_counter()
            times.extend([now] * len(chunk))
            toks.extend(int(t) for t in chunk)
        return cb

    def offer(self, srv, t0, annotate):
        """The generator: sleep until each request is due, submit, note how
        late that was. Nothing else happens here; it has a thread of its
        own."""
        for req, row in zip(self.schedule, self.rows):
            row["due"] = t0 + req["due"]
            wait = row["due"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            row["sent"] = time.perf_counter()
            try:
                with annotate("perfbench.submit"):
                    row["rid"] = srv.submit(
                        req["prompt"], max_new_tokens=req["max_new_tokens"],
                        on_token=self.on_token(row))
            except Exception as e:          # a refusal is a failed request
                row["failed"], row["error"] = True, repr(e)
            row["accepted"] = time.perf_counter()

    def finish(self, failures):
        """``done_at`` for the complete ones; the server's failures."""
        for row in self.rows:
            if row["rid"] in failures:
                row["failed"], row["error"] = True, repr(failures[row["rid"]])
            full = len(row["token_times"]) >= row["max_new_tokens"]
            row["done_at"] = row["token_times"][-1] \
                if full and not row["failed"] else None
        return self.rows


def telemetry_snapshot(srv):
    tele = getattr(srv, "telemetry", None)
    return None if tele is None else tele.registry.snapshot()


def run_window(ctx, srv, schedule, drain_s):
    """Start the server's thread, offer the schedule, drain for up to
    ``drain_s`` after the window, and return what the client saw."""
    import jax
    client = Client(schedule)
    annotate = ctx.annotate
    srv.start()
    stats0 = dict(srv.stats)
    tele0 = telemetry_snapshot(srv)
    mark = ctx.watch.mark()
    t0 = time.perf_counter()
    ctx.window_opens(t0)
    t1 = t0 + ctx.seconds
    gen = threading.Thread(target=client.offer, args=(srv, t0, annotate),
                           daemon=True)
    gen.start()
    traced = None
    if ctx.trace:
        slice_s = min(TRACE_SLICE_S, ctx.seconds / 2.0)
        time.sleep(max(0.0, (ctx.seconds - slice_s) / 2.0))
        jax.profiler.start_trace(ctx.trace_dir)
        with annotate("perfbench.window"):
            time.sleep(slice_s)
        jax.profiler.stop_trace()
        traced = slice_s
    time.sleep(max(0.0, t1 - time.perf_counter()))
    stats1 = dict(srv.stats)
    tele1 = telemetry_snapshot(srv)
    gen.join()
    end = t1 + drain_s
    while time.perf_counter() < end and any(
            r["sent"] is not None and not r["failed"]
            and len(r["token_times"]) < r["max_new_tokens"]
            for r in client.rows):
        time.sleep(0.05)
    compiles, compile_s = ctx.watch.since(mark)
    # the failures so far: the hard stop then fails what is still streaming
    # at the end of the drain, which is cut off, not failed
    failures = dict(srv.failures)
    srv.stop(drain=False, timeout=120.0)
    requests = client.finish(failures)
    errors = [r["error"] for r in requests if r["error"]]
    if errors:
        say(f"{len(errors)} requests failed or were refused; the first: "
            f"{errors[0]}")
    blocked = sorted((r["accepted"] - r["sent"]) * 1e3 for r in requests
                     if r["accepted"] is not None)
    if blocked:
        say(f"submit() held the generator for a median "
            f"{blocked[len(blocked) // 2]:.1f} ms, at worst "
            f"{blocked[-1]:.1f} ms (it takes the server's lock, which a "
            f"tick holds)")
    say(f"compiles inside the window (and its drain): {compiles} "
        f"executables, {compile_s:.2f}s; serving programs "
        f"{dict(ctx.costs.compiles())}")
    queue_wait = {}
    tele = getattr(srv, "telemetry", None)
    if tele is not None:
        for ev in tele.tracer.events():
            if ev["name"] == "request.queued" and "dur" in ev:
                rid = ev.get("args", {}).get("rid")
                queue_wait[rid] = queue_wait.get(rid, 0.0) + ev["dur"] / 1e6
    return {
        "window": {"t0": t0, "t1": t1, "seconds": ctx.seconds,
                   "drain_s": drain_s, "traced_s": traced},
        "requests": requests,
        "server_stats": {"start": stats0, "end": stats1},
        "telemetry": {"start": tele0, "end": tele1,
                      "queue_wait_s": queue_wait} if tele is not None
        else None,
        "slots": srv.max_slots,
        "compiles_in_window": compiles,
        # what an idle gap is called where no host event covers it
        "idle_default": "server thread",
    }


# ------------------------------------------------------------- correctness
def check_tokens(name, ref_logits, prompt, emitted):
    """One request against the reference: every emitted token's reference
    logit within LOGIT_MARGIN_STD reference-logit stds of that position's
    maximum. Returns (ok, worst gap, tokens that are the reference's argmax,
    line)."""
    ids = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    lg = ref_logits(ids)                               # [T, V]
    if not np.isfinite(lg).all():
        return False, float("inf"), 0, f"{name}: reference logits not finite"
    worst, agree = 0.0, 0
    for j, tok in enumerate(emitted):
        row = lg[len(prompt) - 1 + j]                  # predicts token j
        worst = max(worst, float(row.max() - row[int(tok)]) / float(row.std()))
        agree += int(row.argmax() == int(tok))
    ok = worst <= LOGIT_MARGIN_STD
    return ok, worst, agree, (
        f"{name} prompt={len(prompt)} emitted={len(emitted)} "
        f"argmax-agree={agree}/{len(emitted)} worst-gap={worst:.3f} std"
        + ("" if ok else f" MISMATCH (margin {LOGIT_MARGIN_STD})"))


def check_sample(ctx, model, schedule, requests):
    """Replay a seeded sample of completed requests through the plain
    reference, AFTER the window and after the server is gone."""
    done = [i for i, r in enumerate(requests) if r["done_at"] is not None]
    if not done:
        say("correct: no request completed, nothing to compare")
        return False
    rng = np.random.default_rng([int(ctx.seed), 11])
    picks = rng.choice(done, min(CHECKED_REQUESTS, len(done)), replace=False)
    width = ctx.mix["server"]["max_cache_len"]
    params = model.raw_params()

    def ref_logits(ids):
        return ctx.family.reference_row_logits(ctx.config, params, ids,
                                               width, ctx.rehearse)
    ok, worst, agree, emitted = True, 0.0, 0, 0
    for i in picks:
        good, gap, same, line = check_tokens(
            f"request {i}", ref_logits, schedule[i]["prompt"],
            requests[i]["tokens"])
        say("correct: " + line)
        ok, worst = ok and good, max(worst, gap)
        agree, emitted = agree + same, emitted + len(requests[i]["tokens"])
    share = agree / emitted
    say(f"correct: {len(picks)} requests, {emitted} tokens against the f32 "
        f"reference: worst gap {worst:.4f} std (margin {LOGIT_MARGIN_STD}), "
        f"{agree} its argmax = {share:.4f} (at least {ARGMAX_AGREE_MIN})")
    return ok and share >= ARGMAX_AGREE_MIN


def peak_live_pages(requests, page_size):
    """The most pages held at one time, on the client's count: a request
    holds its full extent (``admission="reserve"``: prompt plus the tokens it
    may emit) from its first token to its last."""
    events = []
    for r in requests:
        if r["token_times"]:
            pages = -(-(r["prompt_tokens"] + r["max_new_tokens"]) // page_size)
            events += [(r["token_times"][0], pages),
                       (r["token_times"][-1], -pages)]
    peak = held = 0
    for _, pages in sorted(events):
        held += pages
        peak = max(peak, held)
    return peak


def weights_seed(ctx):
    """The seed the weights are drawn from: the run's own, or the one the
    configuration fixes for every run (``assumed.weights_seed``) where the
    weights would change the work, as a seeded router's choices change how
    many experts a tick reads. ``--seed`` still draws every token."""
    return int(ctx.config.get("assumed", {}).get("weights_seed", ctx.seed))


def prepare(ctx):
    """Set-up of a serving run: model, server, both warm-up passes."""
    import jax
    family, config, mix = ctx.family, ctx.config, ctx.mix
    vocab = family.vocab(config, ctx.rehearse)
    with ctx.phase("model"):
        model = family.build_model(config, weights_seed(ctx), ctx.rehearse)
        jax.block_until_ready(model.raw_params())
    with ctx.phase("server"):
        srv = build_server(ctx, model)
    with ctx.phase("warm-up pass 1"):
        widths, waves = warm_up(srv, mix, vocab, ctx.seed)
    mark = ctx.watch.mark()
    with ctx.phase("warm-up pass 2"):
        warm_up(srv, mix, vocab, ctx.seed + 1)
    rebuilt, _ = ctx.watch.since(mark)
    say(f"warm-up: prefill widths {widths}, activation waves 1..{waves[-1]};"
        f" the second pass built {rebuilt} executables (must be 0)")
    return model, srv, vocab, rebuilt


def serve(ctx, drain_s):
    """One serving run, start to end; returns the observations."""
    from perfbench import stats
    model, srv, vocab, rebuilt = prepare(ctx)
    with ctx.phase("schedule"):
        schedule = traffic.serving_schedule(ctx.mix, ctx.seed, ctx.seconds,
                                            vocab)
    obs = run_window(ctx, srv, schedule, drain_s)
    obs["warmup_rebuilt"] = rebuilt
    w, server = obs["window"], ctx.mix["server"]
    due = stats.due_in_window(obs["requests"], w["t0"], w["t1"])
    obs["attempted"] = len(due)
    obs["failed"] = sum(1 for r in due if r["failed"] or not r["token_times"])
    pages = pool_pages(server)
    live = peak_live_pages(obs["requests"], server["page_size"])
    say(f"window: {len(due)} requests due, {obs['failed']} failed, refused "
        f"or still owed a first token after the drain, "
        f"{sum(1 for r in due if r['done_at'] is not None)} complete; at "
        f"most {live} of {pages - 1} pages held at one time "
        f"({100.0 * live / (pages - 1):.1f}% of the pool)")
    ttft = stats.window_ttfts_ms(obs)
    gaps = stats.token_gaps_ms(obs["requests"], w["t0"], w["t1"])
    say("window: TTFT ms p50/p75/p90/p95/max "
        + " / ".join(f"{stats.percentile(ttft, q):.1f}"
                     for q in (50, 75, 90, 95, 100))
        + f"; token gap ms p50/p95 {stats.percentile(gaps, 50):.2f} / "
        f"{stats.percentile(gaps, 95):.2f} over {len(gaps)} gaps")
    obs["program_temp_bytes"] = max(
        (int(p.executable.memory_analysis().temp_size_in_bytes)
         for _, p in ctx.costs.programs()), default=0)
    obs["runtime_peak_bytes"] = ctx.runtime_peak_bytes()
    del srv
    model.reset_generate_cache()
    gc.collect()
    obs["correct"] = check_sample(ctx, model, schedule, obs["requests"])
    return obs


def sweep(ctx, rates, drain_s=60.0):
    """The builder's tool for an open-loop mix: ONE set-up, then a window at
    each rate with a full drain between, and a line a rate from which the
    knee is read (the highest rate at which the queue does not grow through
    the window). Prints a table, returns nothing the driver reads."""
    from perfbench import stats
    model, srv, vocab, _ = prepare(ctx)
    say("sweep: rate due done  ttft_p50 ttft_p90   itl_p50  itl_p95  "
        "out_tok/s  owed first token at 25/50/75/100% of the window  "
        "most pages held")
    for rate in rates:
        schedule = traffic.serving_schedule(ctx.mix, ctx.seed, ctx.seconds,
                                            vocab, rate_per_s=rate)
        obs = run_window(ctx, srv, schedule, drain_s)
        w, reqs = obs["window"], obs["requests"]
        ttft = stats.window_ttfts_ms(obs)
        gaps = stats.token_gaps_ms(reqs, w["t0"], w["t1"])
        out = sum(1 for r in reqs for t in r["token_times"]
                  if w["t0"] <= t < w["t1"])
        owed = []
        for frac in (0.25, 0.5, 0.75, 1.0):
            at = w["t0"] + frac * w["seconds"]
            owed.append(sum(1 for r in reqs if r["due"] <= at and (
                not r["token_times"] or r["token_times"][0] > at)))
        done = sum(1 for r in reqs if r["done_at"] is not None)
        say(f"sweep: {rate:4.2f} {len(reqs):3d} {done:4d} "
            f"{stats.percentile(ttft, 50):9.1f} "
            f"{stats.percentile(ttft, 90):8.1f} "
            f"{stats.percentile(gaps, 50):9.2f} "
            f"{stats.percentile(gaps, 95):8.2f} "
            f"{out / w['seconds']:10.1f}  {owed}  "
            f"{peak_live_pages(reqs, ctx.mix['server']['page_size'])}")
