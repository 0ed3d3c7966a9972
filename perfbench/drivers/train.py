"""One chip's share of a data-parallel pretraining job: the program's jitted
train step, a new seeded batch from the host every step, steps counted when
their loss has come back."""
import gc
import math
import time

from perfbench import traffic
from perfbench.harness import say

TRACED_STEPS = 5


def run(ctx):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.jit import train_step_fn

    family, config, mix = ctx.family, ctx.config, ctx.mix
    vocab = family.vocab(config, ctx.rehearse)
    tokens = mix["micro_batch"] * mix["seq_len"]

    def batch_of(i):
        ids = jnp.asarray(traffic.train_batch(mix, ctx.seed, i, vocab))
        return {"inputs": (ids,), "labels": (ids,)}

    with ctx.phase("model"):
        model = family.build_model(config, ctx.seed, ctx.rehearse)
        params = model.raw_params()
        jax.block_until_ready(params)
    with ctx.phase("optimizer"):
        opt = pt.optimizer.AdamW(learning_rate=mix["learning_rate"],
                                 parameters=model.parameters())
        init_fn, _ = opt.functional()
        # f32 moments beside bf16 weights (PR 21's recipe)
        state = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                       init_fn(params))
        step = train_step_fn(model, family.ce_loss, opt)
        shapes = {n: tuple(a.shape) for n, a in params.items()}
        flops_per_token = family.train_flops_per_token(
            config, model, mix["seq_len"], ctx.rehearse)
    with ctx.phase("first step"):
        loss, params, state = step(params, state, batch_of(1), 1)
        first_loss = float(loss)
    mark = ctx.watch.mark()
    with ctx.phase("second step"):
        loss, params, state = step(params, state, batch_of(2), 2)
        second_loss = float(loss)
    rebuilt, _ = ctx.watch.since(mark)
    say(f"warm-up: first loss {first_loss:.5f}; the second step built "
        f"{rebuilt} executables (must be 0)")

    # ---- the window: one step in flight while the next batch is fed
    mark = ctx.watch.mark()
    steps, i, pending = [], 2, None
    t0 = time.perf_counter()
    ctx.window_opens(t0)
    t1 = t0 + ctx.seconds
    trace_at = 3 if ctx.trace else None
    traced = None

    def land(p):
        value = float(p[1])                      # waits for that step
        steps.append({"step": p[0], "end": time.perf_counter(),
                      "tokens": tokens, "loss": value})

    while True:
        if trace_at is not None and len(steps) >= trace_at:
            if pending is not None:
                land(pending)
                pending = None
            jax.profiler.start_trace(ctx.trace_dir)
            t_tr = time.perf_counter()
            with ctx.annotate("perfbench.window"):
                for _ in range(TRACED_STEPS):
                    i += 1
                    with ctx.annotate("perfbench.feed_and_dispatch"):
                        loss, params, state = step(params, state,
                                                   batch_of(i), i)
                    with ctx.annotate("perfbench.wait_for_loss"):
                        land((i, loss))
            traced = time.perf_counter() - t_tr
            jax.profiler.stop_trace()
            trace_at = None
        i += 1
        loss, params, state = step(params, state, batch_of(i), i)
        if pending is not None:
            land(pending)
        pending = (i, loss)
        if steps and steps[-1]["end"] > t1:
            break
    land(pending)
    compiles, compile_s = ctx.watch.since(mark)
    say(f"compiles inside the window: {compiles} executables, "
        f"{compile_s:.2f}s")
    in_window = [s for s in steps if s["end"] <= t1]
    say(f"window: {len(in_window)} steps ended inside {ctx.seconds:.0f}s, "
        f"loss {steps[0]['loss']:.4f} -> {steps[-1]['loss']:.4f}")

    obs = {
        "window": {"t0": t0, "t1": t1, "seconds": ctx.seconds,
                   "traced_s": traced},
        "steps": steps,
        "attempted": len(in_window),
        "failed": sum(1 for s in in_window if not math.isfinite(s["loss"])),
        "train": {"tokens_per_step": tokens,
                  "flops_per_token": flops_per_token},
        "compiles_in_window": compiles,
        "idle_default": "host (no event)",
        "warmup_rebuilt": rebuilt,
        "runtime_peak_bytes": ctx.runtime_peak_bytes(),
    }
    exe = step.lower(params, state, batch_of(1), 1).compile()
    obs["program_temp_bytes"] = int(exe.memory_analysis().temp_size_in_bytes)

    # ---- correctness, after the window: the first loss against the plain
    # reference on the same weights (made again from the seed) and batch
    del params, state, exe, loss, pending
    gc.collect()
    fresh = family.init_params(shapes, ctx.seed, jnp.bfloat16)
    want = family.reference_loss(config, fresh,
                                 traffic.train_batch(mix, ctx.seed, 1, vocab),
                                 ctx.rehearse)
    tol = mix["loss_tolerance"]
    finite = all(math.isfinite(x) for x in
                 [first_loss, second_loss] + [s["loss"] for s in steps])
    close = abs(first_loss - want) <= tol * abs(want)
    say(f"correct: first loss {first_loss:.6f} against the f32 reference's "
        f"{want:.6f}: off by {abs(first_loss - want) / abs(want):.2e} of it "
        f"(tolerance {tol:.1e}); every loss finite: {finite}")
    # a window in which no step ended measured nothing
    obs["correct"] = bool(finite and close and in_window)
    return obs
