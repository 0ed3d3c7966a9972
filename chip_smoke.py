#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of GPT-2 345M (24 layers, hidden 1024, 16 heads x 64, vocab
50304, context 1024; bf16, seeded random weights), in ONE process:

- ``kernels``: every Pallas kernel the next phases use, compiled by Mosaic
  at the server's / trainer's geometry, against its own ``_ref_*``
  composition under ``jax.default_matmul_precision("highest")``.
- ``serve``: ``ContinuousBatchingServer(cache_backend="paged")`` (ragged
  prefill + split tick) answering mixed-length requests, checked on logits
  against a plain f32 forward.
- ``hybrid-serve``: the same server over a model whose layers are NOT alike
  (the ``lfm2`` family: gated short-convolution layers with per-slot state
  beside the page pool, attention layers, dense and routed-expert FFNs in one
  layer loop), at small lane-legal widths, prompts spanning several launches
  with other slots decoding between them, checked on logits against the
  model's own uncached f32 forward. The benchmark's third configuration runs
  these programs at published widths; this phase compiles them for the chip
  outside the benchmark too.
- ``ssm-serve``: the same server over a ``nemotron_h``-shaped model (a layer
  is ONE sublayer: Mamba-2 layers whose float32 recurrent state and
  convolution window are a per-slot state TREE, an attention layer with no
  positional term, latent expert layers that hold half of the router's
  experts) at small lane-legal widths, prompts spanning several launches and
  several chunks of the scan, checked on logits against the model's own
  uncached f32 forward. The benchmark's fourth configuration runs these
  programs at published widths.
- ``train``: ``jit.train_step_fn(model, ce, AdamW)`` at B=8, S=1024.
- ``mesh4`` (only where JAX reports >= 4 devices): the serve phase over an
  ``mp=4`` mesh plus ``parallel.parallel_train_step``.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
when JAX runs on a TPU and every phase passed. Anything else — no
accelerator, a failed phase — exits non-zero. ``--rehearse`` runs the same
phases at a tiny preset on the CPU with the kernels in Pallas interpret
mode, every line marked ``REHEARSAL``; it proves the script, never the chip.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

# ---------------------------------------------------------------- tolerances
# Every kernel casts its operands to float32 and keeps its online softmax
# in float32, but its MXU products follow JAX's matmul precision like any
# XLA dot: on this chip the default is ONE bf16 pass (measured: the f32
# paged kernel is 2.4e-3 of the output scale off the highest-precision
# reference — one bf16 rounding, 2**-9).
#
# F32_TOL judges the kernels' logic (masks, page walks, online softmax):
# f32 in, f32 out, compiled under jax.default_matmul_precision("highest"),
# only summation order and the exp unit differ from the reference, ~1e-6
# of the output scale (largest measured on the chip: 5.2e-5, flash out).
# One bf16 rounding anywhere costs >= 2**-9 ~ 2e-3 — 10x above this
# bound.
F32_TOL = 2e-4
# DEFAULT_TOL judges what the server and trainer actually run: default
# precision, f32 or bf16 operands. Products and probabilities round to
# bf16 (8 significand bits, half-ulp 2**-9) and bf16 outputs round once
# more; gradients also see rounded residuals (o, do), so they get 2**-6.
DEFAULT_TOL = 2.0 ** -7
DEFAULT_GRAD_TOL = 2.0 ** -6
# serve check, in units of the reference logits' std over the vocabulary
# at that position: bf16 through 24 layers perturbs the final hidden state
# by a few percent, and a logit moves by that share of the logit spread, so
# the token the bf16 server emits must sit within a fraction of one std of
# the f32 maximum. A token chosen by a broken path is a random draw:
# ~4 std below the maximum of 50k logits.
LOGIT_MARGIN_STD = 0.5
# compiled serving programs may not carry the weights: generated code under
# this share of the weight bytes (the parent commit's decode tick held 100%)
CODE_SHARE_MAX = 0.10

TAG = ""          # "REHEARSAL " under --rehearse


def say(msg=""):
    for line in str(msg).splitlines() or [""]:
        print(TAG + line, flush=True)


# ------------------------------------------------------------------ presets
def presets(rehearse):
    from paddle_tpu.models.gpt import GPTConfig, gpt2_345m
    if not rehearse:
        cfg = gpt2_345m(dropout=0.0)
        # prompt lengths share one pow2 bucket (32, 64] so every ragged
        # launch pads to the same chunk width whatever the thread timing:
        # 33 and 49 sit one past a page multiple, 64 is the prefix donor
        return dict(cfg=cfg, slots=8, cache_len=1024, page=16,
                    prompts=(33, 40, 49, 57, 64), shared=48, tail=40,
                    new=16, train_b=8, train_s=1024, lr=1e-3, steps=5)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    return dict(cfg=cfg, slots=4, cache_len=64, page=8,
                prompts=(9, 12, 11, 16), shared=16, tail=10, new=6,
                train_b=2, train_s=128, lr=1e-3, steps=4)


# ------------------------------------------------------- compile accounting
class CompileWatch:
    """Every executable JAX builds or loads in this process (jit, AOT and
    eager alike), counted from JAX's own monitoring events, plus the
    persistent-cache hits among them."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.count, self.seconds)

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]


def cache_entries(path):
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except OSError:
        return 0


def mem_line(label, extra=""):
    """bytes_in_use / peak per device, straight from the runtime (not the
    paddle_tpu.device wrapper, which answers {} on error)."""
    import jax
    parts = []
    for d in jax.devices():
        st = d.memory_stats()
        if st:
            parts.append(
                f"dev{d.id}: in_use={st['bytes_in_use'] / 2**30:.3f} GiB "
                f"peak={st['peak_bytes_in_use'] / 2**30:.3f} GiB")
    say(f"  memory after {label}: "
        + ("; ".join(parts) or "memory_stats() n/a on this platform") + extra)


def tree_bytes(tree):
    import jax
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def root_cause(exc):
    """The first underlying error: a compile error inside the serving
    thread is retried by the supervisor and reaches wait() wrapped in a
    CircuitOpenError — show the cause, not the breaker."""
    seen = set()
    while exc.__cause__ is not None and id(exc) not in seen:
        seen.add(id(exc))
        exc = exc.__cause__
    return exc


class Phases:
    def __init__(self, watch):
        self.watch = watch
        self.failed = []

    @contextlib.contextmanager
    def run(self, name):
        say(f"phase {name}: start")
        t0 = time.perf_counter()
        mark = self.watch.mark()
        try:
            yield
        except Exception as e:       # boundary: report, keep other phases
            cause = root_cause(e)
            n, cs = self.watch.since(mark)
            say(f"phase {name}: FAILED in {time.perf_counter() - t0:.1f}s "
                f"(compile {cs:.1f}s in {n} programs): "
                f"{type(cause).__name__}: {cause}")
            say("".join(traceback.format_exception(cause)))
            self.failed.append(name)
        else:
            n, cs = self.watch.since(mark)
            say(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s "
                f"(compile {cs:.1f}s in {n} programs)")


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ================================================================== kernels
class KernelCase:
    """One (dtype, matmul precision) setting for the kernel checks, the
    geometry the server and trainer pass, and the mismatches so far."""

    def __init__(self, P, rehearse, dtype, highest, tol, grad_tol):
        import jax.numpy as jnp
        cfg = P["cfg"]
        self.P, self.rehearse = P, rehearse
        self.dtype, self.highest = dtype, highest
        self.tol, self.grad_tol = tol, grad_tol
        self.nh = cfg.num_heads
        self.hd = cfg.hidden_size // cfg.num_heads
        self.S, self.pg = P["slots"], P["page"]
        self.maxp = P["cache_len"] // P["page"]
        self.T = self.maxp * self.pg
        self.scale = 1.0 / float(np.sqrt(self.hd))
        self.name = (f"{jnp.dtype(dtype).name}"
                     f"{'@highest' if highest else ''}")
        self.rng = np.random.default_rng(7)
        self.bad = []

    def precision(self, on=True):
        import jax
        return (jax.default_matmul_precision("highest") if on
                else contextlib.nullcontext())

    def run(self, fn, args):
        """Compile ``fn`` ahead of time at this case's precision, prove
        the executable holds a Mosaic call (on the chip), run it."""
        import jax
        with self.precision(self.highest):
            compiled = jax.jit(fn).lower(*args).compile()
        if not self.rehearse:
            n = compiled.as_text().count("tpu_custom_call")
            check(n >= 1, "compiled program holds no tpu_custom_call: "
                          "the kernel gave way to its reference")
        return jax.block_until_ready(compiled(*args))

    def ref(self, fn, *args):
        """The kernel's own XLA composition on f32 copies of the same
        inputs, under the highest matmul precision."""
        import jax
        import jax.numpy as jnp
        up = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
              else a for a in args]
        with self.precision():
            return jax.jit(fn)(*up)

    def close(self, name, got, want, tol=None):
        """max |got - want| <= tol * max |want| (the output's scale)."""
        import jax.numpy as jnp
        tol = self.tol if tol is None else tol
        name = f"{name}[{self.name}]"
        got = jnp.asarray(got, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        scale = float(jnp.abs(want).max())
        err = float(jnp.abs(got - want).max())
        ok = bool(jnp.isfinite(got).all()) and err <= tol * scale
        say(f"    {name:<42} max_err={err:.3e} scale={scale:.3e} "
            f"tol={tol * scale:.3e} {'ok' if ok else 'MISMATCH'}")
        if not ok:
            self.bad.append(f"{name}: max error {err:.3e} exceeds "
                            f"{tol:.1e} x output scale {scale:.3e}")

    def pool(self, live_tokens):
        """K/V pool and block tables (live pages distinct and shuffled,
        tails on the null page 0, as the allocator leaves them)."""
        import jax.numpy as jnp
        num_pages = self.S * self.maxp + 1
        shape = (num_pages, self.pg, self.nh, self.hd)
        k = jnp.asarray(self.rng.standard_normal(shape), self.dtype)
        v = jnp.asarray(self.rng.standard_normal(shape), self.dtype)
        bt = np.zeros((self.S, self.maxp), np.int32)
        free = self.rng.permutation(np.arange(1, num_pages))
        at = 0
        for s, n in enumerate(live_tokens):
            need = -(-int(n) // self.pg)
            bt[s, :need] = free[at:at + need]
            at += need
        return k, v, jnp.asarray(bt)

    def normal(self, *shape):
        import jax.numpy as jnp
        return jnp.asarray(self.rng.standard_normal(shape), self.dtype)

    def same_at_layer(self, name, call, got, q, k, v, rest):
        """The serving loop's call of a paged kernel: the WHOLE pool,
        lane-dense, read through a layer index. The one-layer pools
        k/v sit at layer 1 of two (layer 0 holds other values) and the
        output must equal ``got``, the one-layer call's, bit for bit."""
        import jax.numpy as jnp

        from paddle_tpu.models.generation import pool_lanes
        kk, vv = (pool_lanes(jnp.stack([-a, a])) for a in (k, v))
        at1 = self.run(lambda q, k, v, *r: call(q, k, v, *r, layer=1),
                       (q, kk, vv) + rest)
        if not bool((at1 == got).all()):
            self.bad.append(f"{name}[{self.name}]: the pool read at a "
                            f"layer index differs from that layer alone")


def kernel_paged(c):
    """Decode attention over the live pages' grid: ragged lengths incl.
    1, a page boundary, one past it and a full table; the same with
    every other slot idle; ONE live slot; a full house; nothing live.
    A slot of length 0 must come back as exact zeros."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention as pa
    pg, T = c.pg, c.T
    ragged = np.array([1, pg, pg + 1, T, 3 * pg + 5, 2, T // 2, T - 1][:c.S],
                      np.int32)
    idle = np.arange(c.S) % 2 == 1
    one = np.zeros(c.S, np.int32)
    one[c.S // 2] = 3 * pg + 5
    q = c.normal(c.S, c.nh, c.hd)

    def call(q, k, v, bt, ln, layer=None):
        return pa.paged_attention(q, k, v, bt, ln, c.scale, layer=layer)

    for tag, lens in (("ragged", ragged),
                      ("half idle", np.where(idle, 0, ragged)),
                      ("one live", one),
                      ("full house", np.full(c.S, T, np.int32)),
                      ("none live", np.zeros(c.S, np.int32))):
        k, v, bt = c.pool(lens)
        lens_d = jnp.asarray(lens)
        got = c.run(call, (q, k, v, bt, lens_d))
        if tag == "ragged":
            c.same_at_layer("paged_attention", call, got, q, k, v,
                            (bt, lens_d))
        if not bool((got[lens == 0] == 0).all()):
            c.bad.append(f"paged_attention {tag}[{c.name}]: a slot of "
                         f"length 0 is not zeros")
        if lens.any():
            want = c.ref(lambda q, k, v, bt, ln: pa._ref_paged_attention(
                q, k, v, bt, ln, c.scale), q, k, v, bt, lens_d)
            c.close(f"paged_attention {tag}", got[lens > 0],
                    want[lens > 0])


def _live_rows(C, take):
    import jax.numpy as jnp
    return jnp.asarray(np.arange(C)[None] < take[:, None])[:, :, None, None]


def kernel_ragged(c):
    """Ragged prefill over the live query tiles' grid: a chunk of four
    tiles, prefix offsets t0 > 0, ragged takes (one ends mid-tile, one
    on a tile's edge), an idle slot (take = 0); the same with ONE slot
    live; nothing live. Rows past a take's last live tile must come
    back as exact zeros."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ragged_prefill as rp
    pg, T = c.pg, c.T
    C = 4 * rp.QUERY_TILE
    t0 = np.array([0, pg, T, 3 * pg + 4, 5, 0, 2 * pg, 7][:c.S], np.int32)
    ragged = np.array([C, C - 12, 0, C, 1, pg + 1, pg, 9][:c.S], np.int32)
    one = np.where(np.arange(c.S) == c.S // 2 + 1, ragged, 0).astype(np.int32)
    q = c.normal(c.S, C, c.nh, c.hd)

    def call(q, k, v, bt, t0, take, layer=None):
        return rp.ragged_prefill_attention(q, k, v, bt, t0, take=take,
                                           sm_scale=c.scale, layer=layer)

    for tag, take in (("ragged", ragged), ("one live", one),
                      ("none live", np.zeros(c.S, np.int32))):
        name = f"ragged_prefill {tag} C={C}"
        k, v, bt = c.pool(np.where(take > 0, t0 + take, 0))
        t0_d, take_d = jnp.asarray(t0), jnp.asarray(take)
        got = c.run(call, (q, k, v, bt, t0_d, take_d))
        if tag == "ragged":
            c.same_at_layer(name, call, got, q, k, v, (bt, t0_d, take_d))
        tiles = -(-take // rp.QUERY_TILE) * rp.QUERY_TILE
        if not bool(jnp.where(_live_rows(C, tiles), True, got == 0).all()):
            c.bad.append(f"{name}[{c.name}]: rows of a query tile no "
                         f"grid step visits must read as zeros")
        if take.any():
            want = c.ref(lambda q, k, v, bt, t0: rp._ref_ragged_prefill(
                q, k, v, bt, t0, c.scale), q, k, v, bt, t0_d)
            live = _live_rows(C, take)     # rows past a take are padding
            c.close(name, jnp.where(live, got.astype(jnp.float32), 0.0),
                    jnp.where(live, want, 0.0))


def kernel_flash(c):
    """Flash attention at the trainer's geometry: forward and the three
    gradients against a seeded cotangent."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa
    B, Ts = c.P["train_b"], c.P["train_s"]
    if c.highest and not c.rehearse:
        # under "highest" the backward kernels' 1024 x 1024 f32 tiles ask
        # Mosaic for 17.4 MiB of scoped VMEM (limit 16): the logic check
        # runs at half the sequence, one 512-block per row like the
        # trainer's one 1024-block
        Ts //= 2
    q, k, v, w = (c.normal(B, c.nh, Ts, c.hd) for _ in range(4))

    def fwd_and_grads(attend):
        def run(q, k, v, w):
            def loss(q, k, v):
                o = attend(q, k, v)
                return (o.astype(jnp.float32) * w.astype(jnp.float32)
                        ).sum(), o
            (_, o), g = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (o,) + g
        return run

    check(fa._pallas_ok(q, k), "the trainer's geometry does not take the "
                               "Pallas flash path")
    got = c.run(fwd_and_grads(
        lambda q, k, v: fa._flash(q, k, v, c.scale, True)), (q, k, v, w))
    want = c.ref(fwd_and_grads(
        lambda q, k, v: fa._ref_attention(q, k, v, c.scale, True)),
        q, k, v, w)
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        c.close(f"flash_attention {name}", g, r,
                c.tol if name == "out" else c.grad_tol)


def kernel_qmm(P, rehearse):
    """int8 matmul with fused dequant (the PTQ deploy kernel): the int32
    accumulation is exact, so only the f32 epilogue may differ."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import quant_matmul as qm
    c = KernelCase(P, rehearse, jnp.int8, False, 1e-6, 1e-6)
    m, kk, n = (256, 256, 256) if rehearse else (512, 1024, 1024)
    x = jnp.asarray(c.rng.integers(-127, 128, (m, kk)), jnp.int8)
    wq = jnp.asarray(c.rng.integers(-127, 128, (kk, n)), jnp.int8)
    sx = jnp.float32(0.013)
    sw = jnp.asarray(c.rng.uniform(0.001, 0.02, (n,)), jnp.float32)
    got = c.run(lambda x, w, sx, sw: qm.quantized_matmul(x, w, sx, sw),
                (x, wq, sx, sw))
    acc = jax.lax.dot_general(x, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    c.close("quantized_matmul", got, acc.astype(jnp.float32) * sx * sw[None])
    return c.bad


def kernel_cases(P, rehearse):
    """f32 under the highest matmul precision checks the kernels' LOGIC
    to F32_TOL; the other two run what the chip runs by default."""
    import jax.numpy as jnp
    return [KernelCase(P, rehearse, jnp.float32, True, F32_TOL, F32_TOL),
            KernelCase(P, rehearse, jnp.float32, False, DEFAULT_TOL,
                       DEFAULT_GRAD_TOL),
            KernelCase(P, rehearse, jnp.bfloat16, False, DEFAULT_TOL,
                       DEFAULT_GRAD_TOL)]


def phase_kernels(P, rehearse):
    bad = []
    for case in kernel_cases(P, rehearse):
        for kernel in (kernel_paged, kernel_ragged, kernel_flash):
            kernel(case)
        bad += case.bad
    bad += kernel_qmm(P, rehearse)
    check(not bad, "; ".join(bad))


# ==================================================================== serve
def build_model(P, seed=0):
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM
    pt.seed(seed)
    model = GPTForCausalLM(P["cfg"])
    model.eval()
    model.astype("bfloat16")
    return model


def make_requests(P, seed):
    """Seeded prompts of mixed length, and a tail request that shares its
    first ``shared`` tokens with the longest of them (the donor, served a
    wave earlier). Fresh tokens per seed: a repeated prompt would hit the
    prefix cache whole and change the launch shapes."""
    rng = np.random.default_rng(seed)
    V = P["cfg"].vocab_size
    wave = [rng.integers(0, V, (n,)).astype(np.int32) for n in P["prompts"]]
    donor = max(wave, key=len)
    tail = np.concatenate([donor[:P["shared"]],
                           rng.integers(0, V, (P["tail"],)).astype(np.int32)])
    return wave, tail


def reference_logits_fn(model, width):
    """One plain f32 forward of the model over a right-padded [1, width]
    row (causal: padding cannot reach earlier positions), weights passed as
    arguments, under highest matmul precision. ``width`` is no multiple of
    128, so the attention inside is the XLA composition, not a kernel."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit import functional_call
    params32 = {n: a.astype(jnp.float32)
                for n, a in model.raw_params().items()}
    fwd = jax.jit(lambda ps, ids: functional_call(model, ps, ids))

    def logits(ids):
        row = np.zeros((1, width), np.int32)
        row[0, :len(ids)] = ids
        with jax.default_matmul_precision("highest"):
            out = fwd(params32, jnp.asarray(row))
        check(out.dtype == jnp.float32, f"reference ran in {out.dtype}")
        return np.asarray(out[0, :len(ids)])

    return logits


def check_tokens(name, ref_logits, prompt, emitted):
    """Every emitted token's reference logit within LOGIT_MARGIN_STD
    reference-logit stds of that position's maximum."""
    ids = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    lg = ref_logits(ids)                               # [T, V]
    check(np.isfinite(lg).all(), f"{name}: reference logits not finite")
    worst, agree, lead = 0.0, 0, []
    for j, tok in enumerate(emitted):
        row = lg[len(prompt) - 1 + j]                  # predicts token j
        top2 = np.partition(row, -2)[-2:]
        lead.append(float(top2[1] - top2[0]) / float(row.std()))
        gap = float(row.max() - row[int(tok)]) / float(row.std())
        worst = max(worst, gap)
        agree += int(row.argmax() == int(tok))
    say(f"    {name:<22} prompt={len(prompt):<4} emitted={len(emitted):<3} "
        f"argmax-agree={agree}/{len(emitted)} worst-gap={worst:.3f} std "
        f"(margin {LOGIT_MARGIN_STD}; the reference's own top-1 leads its "
        f"top-2 by a median {np.median(lead):.2f} std)")
    check(worst <= LOGIT_MARGIN_STD,
          f"{name}: an emitted token sits {worst:.3f} logit-stds below "
          f"the f32 reference's maximum (margin {LOGIT_MARGIN_STD})")


def inspect_programs(cat, weight_bytes, pool_bytes, rehearse):
    """Every serving program the catalog compiled: generated code under
    CODE_SHARE_MAX of the weights, temporaries under half the page pool
    (a tick that copies, slices or relays out the pool shows up as a
    pool-sized temp: tests/test_tick_programs_v5e.py holds the same
    here, without the chip), and a Mosaic call in every decode and every
    prefill program (the prefill program loops over its query tiles: one
    kernel call)."""
    seen = {}
    for op, prog in cat.programs():
        exe = prog.executable
        mem = exe.memory_analysis()
        code = int(mem.generated_code_size_in_bytes)
        calls = exe.as_text().count("tpu_custom_call")
        say(f"    program {op:<8} code={code / 2**20:8.2f} MiB "
            f"({code / weight_bytes:6.2%} of weights) "
            f"args={mem.argument_size_in_bytes / 2**30:.3f} GiB "
            f"temp={mem.temp_size_in_bytes / 2**30:.3f} GiB "
            f"tpu_custom_call={calls} compile={prog.compile_s:.1f}s")
        seen.setdefault(op, []).append(calls)
        if rehearse:
            continue
        check(code <= CODE_SHARE_MAX * weight_bytes,
              f"{op}: generated code {code} B is over "
              f"{CODE_SHARE_MAX:.0%} of the weights ({weight_bytes} B): "
              f"the weights are constants of the executable")
        check(mem.temp_size_in_bytes <= pool_bytes / 2,
              f"{op}: temp {mem.temp_size_in_bytes} B is over half the "
              f"page pool ({pool_bytes} B): the program holds a copy of "
              f"the pool instead of updating it in place")
    for op in ("decode", "prefill"):
        got = [c for o, cs in seen.items() if o.split("_mp")[0] == op
               for c in cs]
        check(got, f"no {op!r} program was compiled")
        if not rehearse:
            check(min(got) >= 1,
                  f"{op}: a compiled program holds no Mosaic call: a "
                  f"kernel gave way to its reference")


def serve_once(P, model, mesh, rehearse, watch, ref_logits, also_resident):
    from paddle_tpu.inference import ContinuousBatchingServer
    from paddle_tpu.telemetry import CostCatalog

    cat = CostCatalog()
    srv = ContinuousBatchingServer(
        model, cache_backend="paged", max_slots=P["slots"],
        max_cache_len=P["cache_len"], page_size=P["page"],
        mesh=mesh, costs=cat)
    check(srv.prefill_mode == "ragged", "ragged prefill is not the default")
    (w_tree,) = model._pt_stacked_weights.values()
    w_bytes, pool_bytes = tree_bytes(w_tree), tree_bytes(srv._caches["pool"])
    say(f"  serve: stacked weights {w_bytes / 2**30:.3f} GiB (one "
        f"tree, shared by the dense and paged bundles), pool "
        f"{pool_bytes / 2**30:.3f} GiB")
    if mesh is not None:
        n = len(mesh.devices.flat)
        for label, arr in (("pool.k", srv._caches["pool"]["k"]),
                           ("mlp.fc1.weight", w_tree["mlp.fc1.weight"])):
            shard = arr.addressable_shards[0].data.nbytes
            say(f"    {label}: {shard} B per device of {arr.nbytes} B")
            check(shard * n == arr.nbytes,
                  f"{label} is not split {n} ways over the mesh")

    warm_wave, warm_tail = make_requests(P, seed=3)
    wave, tail = make_requests(P, seed=4)
    new = P["new"]
    results = {}

    def drain(tag, prompts):
        rids = [srv.submit(p, max_new_tokens=new) for p in prompts]
        for rid, p in zip(rids, prompts):
            out = np.asarray(srv.wait(rid, timeout=900.0))
            check(len(out) == new, f"{tag}: request {rid} returned "
                                   f"{len(out)} of {new} tokens")
            results[(tag, len(results))] = (p, out)

    # warm-up wave: queued BEFORE start() so the first tick admits it
    # whole; the prefix tail follows once its donor has been harvested
    rids = [srv.submit(p, max_new_tokens=new) for p in warm_wave]
    srv.start()
    try:
        for rid in rids:
            check(len(srv.wait(rid, timeout=900.0)) == new,
                  "warm-up request returned short")
        drain("warm-tail", [warm_tail])
        warm_compiles = dict(cat.compiles())
        mark = watch.mark()
        # measured wave: the same shapes, fresh tokens, on the live server
        drain("wave", wave)
        drain("tail", [tail])
    finally:
        srv.stop(drain=True, timeout=900.0)
    n_all, _ = watch.since(mark)
    after = dict(cat.compiles())
    say(f"  serve: serving-program compiles warm-up {warm_compiles}"
        f" -> after {after}; every executable built after warm-up "
        f"(eager ops included): {n_all}")
    check(after == warm_compiles,
          f"a serving program compiled after the warm-up wave: "
          f"{warm_compiles} -> {after}")
    hits = srv.stats["prefix_auto_hits"]
    check(hits == 2, f"exactly the two shared-prefix requests should hit "
                     f"the prefix cache (prefix_auto_hits={hits})")
    check(cat.price_errors == 0, "the cost catalog could not compile a "
                                 "serving program ahead of time")
    free, live, pinned, cached = srv.pool_balance()
    check(live == 0, f"pages leaked: pool_balance() live == {live}")
    say(f"  serve: prefix hits {hits} "
        f"({srv.stats['prefix_auto_hit_tokens']} tokens), pool free={free} "
        f"live={live} pinned={pinned} cached={cached}, "
        f"dispatches {srv.stats['tick_dispatches']} ticks / "
        f"{srv.stats['prefill_dispatches']} prefill; the prefill "
        f"kernel's grid took {srv.stats['prefill_grid_steps']} steps, "
        f"{srv.stats['prefill_live_steps']} on a live tile's page")
    for (tag, i), (p, out) in results.items():
        if tag != "warm-tail":
            check_tokens(f"{tag}#{i}", ref_logits, p, out)
    inspect_programs(cat, w_bytes, pool_bytes, rehearse)
    resident = dict({"stacked weights": w_bytes, "pool": pool_bytes},
                    **also_resident)
    mem_line("serve", "; known residents: " + " + ".join(
        f"{k} {b / 2**30:.3f}" for k, b in resident.items())
        + f" = {sum(resident.values()) / 2**30:.3f} GiB")
    del srv, cat, w_tree
    gc.collect()


def phase_serve(P, phases, rehearse, watch, mesh=None, label="serve"):
    model = build_model(P)
    width = max(P["prompts"]) + P["tail"] + P["new"]
    width += 8 if width % 128 == 0 else 0
    ref_logits = reference_logits_fn(model, width)
    own = tree_bytes(model.raw_params())
    also = {"the model's own parameters": own,
            "their f32 copy for the reference": 2 * own}
    with phases.run(f"{label}-split"):
        serve_once(P, model, mesh, rehearse, watch, ref_logits, also)
    model.reset_generate_cache()
    del model, ref_logits
    gc.collect()


# =================================================================== hybrid
def hybrid_preset(rehearse):
    """A 6-layer ``lfm2``-shaped model (conv conv | attn conv attn conv; 2
    dense layers, then 8 experts top-2) at lane-legal widths: hidden 256,
    heads of 64, 2 K/V heads (128 pool lanes). The experts' ``w2`` is drawn
    at a tenth of the range, as the benchmark's configuration draws it, so
    that a rounding that flips an untrained router's fourth choice does not
    move the logits."""
    from paddle_tpu.models.lfm2 import lfm2_tiny
    if rehearse:
        return dict(cfg=lfm2_tiny(), slots=4, cache_len=64, page=8,
                    prompts=(9, 20, 13, 6), new=5, budget=8)
    cfg = lfm2_tiny(vocab_size=1024, hidden_size=256, intermediate_size=512,
                    moe_intermediate_size=128, max_position_embeddings=1024,
                    dtype="bfloat16")
    return dict(cfg=cfg, slots=8, cache_len=512, page=16,
                prompts=(33, 150, 90, 57, 200, 17), new=12, budget=64)


def serve_two_waves(tag, model, forward, H):
    """A paged server over ``model`` answering ``H["prompts"]`` twice (the
    second wave lands in slots the first one left), every request checked
    on logits against ``forward(params, ids)``, the model's own uncached
    forward, in float32. Returns the server, drained."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import ContinuousBatchingServer
    cfg = H["cfg"]
    params32 = {n: a.astype(jnp.float32)
                for n, a in model.raw_params().items()}
    fwd = jax.jit(forward)
    width = max(H["prompts"]) + H["new"]

    def ref_logits(ids):
        row = np.zeros((1, width), np.int32)
        row[0, :len(ids)] = ids
        with jax.default_matmul_precision("highest"):
            out = fwd(params32, jnp.asarray(row))
        check(out.dtype == jnp.float32, f"reference ran in {out.dtype}")
        return np.asarray(out[0, :len(ids)])

    srv = ContinuousBatchingServer(
        model, cache_backend="paged", max_slots=H["slots"],
        max_cache_len=H["cache_len"], page_size=H["page"],
        prefill_tokens_per_tick=H["budget"])
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in H["prompts"]]
    for wave in range(2):
        rids = [srv.submit(p, max_new_tokens=H["new"]) for p in prompts]
        outs = srv.run()
        for i, (rid, p) in enumerate(zip(rids, prompts)):
            check(len(outs[rid]) == H["new"], f"request {rid} is short")
            check_tokens(f"{tag} wave {wave} #{i}", ref_logits, p, outs[rid])
    check(srv.stats["prefill_chunks_carried"] > 0,
          "no prompt spanned two launches")
    free, live, *_ = srv.pool_balance()
    check(live == 0, f"pages leaked: pool_balance() live == {live}")
    return srv


def phase_hybrid(phases, rehearse, watch):
    from paddle_tpu.models import lfm2
    H = hybrid_preset(rehearse)
    cfg = H["cfg"]
    with phases.run("hybrid-serve"):
        model = lfm2.Lfm2MoeForCausalLM(cfg, weights=lfm2.init_weights(
            cfg, seed=0, scale={"model.moe_layers.experts_w2": 0.1}))
        model.eval()
        srv = serve_two_waves(
            "hybrid", model, lambda ps, ids: lfm2._forward(cfg, ids, ps), H)
        layers = lfm2.layer_counts(cfg)
        check(srv._caches["pool"]["k"].shape[0] == layers[0],
              "the pool has a layer that is no attention layer's")
        check(srv._caches["state"].shape[:2] == (layers[1], H["slots"]),
              "the slot state is not [conv layers, slots, ...]")
        s = srv.stats
        say(f"  hybrid: {s['prefill_chunks']} slot-chunks, "
            f"{s['prefill_chunks_carried']} carried state; "
            f"{s['decode_ticks']} decode ticks touched "
            f"{s['moe_experts_touched']} experts")
        mem_line("hybrid-serve")
    gc.collect()


# ============================================================== state space
def ssm_preset(rehearse):
    """A ``nemotron_h``-shaped model ``MEM*E`` (two Mamba-2 layers, two
    latent expert layers holding 8 of the router's 16 experts top-3, one
    attention layer) at lane-legal widths: hidden 256, 8 Mamba heads of 64 in
    2 groups with a state of 128 and chunks of 32, 4 query / 2 K/V heads of
    64 (128 pool lanes). The convolution's taps are drawn 25 times the range,
    as the benchmark's configuration draws them, so that the recurrent state
    moves the logits."""
    from paddle_tpu.models.nemotron_h import nemotron_h_tiny
    if rehearse:
        return dict(cfg=nemotron_h_tiny(), slots=4, cache_len=64, page=8,
                    prompts=(9, 20, 13, 6), new=5, budget=8)
    cfg = nemotron_h_tiny(
        vocab_size=1024, hidden_size=256, mamba_num_heads=8,
        mamba_head_dim=64, ssm_state_size=128, n_groups=2, chunk_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        moe_intermediate_size=256, moe_latent_size=128,
        moe_shared_expert_intermediate_size=512,
        max_position_embeddings=1024, dtype="bfloat16")
    return dict(cfg=cfg, slots=8, cache_len=512, page=16,
                prompts=(33, 150, 90, 57, 200, 17), new=12, budget=64)


def phase_ssm(phases, rehearse, watch):
    import jax.numpy as jnp

    from paddle_tpu.models import nemotron_h as nh
    H = ssm_preset(rehearse)
    cfg = H["cfg"]
    with phases.run("ssm-serve"):
        model = nh.NemotronHForCausalLM(cfg, weights=nh.init_weights(
            cfg, seed=0, scale={"model.mamba_layers.conv_weight": 25.0}))
        model.eval()
        srv = serve_two_waves(
            "ssm", model, lambda ps, ids: nh._forward(cfg, ids, ps), H)
        mamba, attn, _ = nh.layer_counts(cfg)
        state = srv._caches["state"]
        check(srv._caches["pool"]["k"].shape[0] == attn,
              "the pool has a layer that is no attention layer's")
        check(state["ssm"].shape[:2] == (mamba, H["slots"])
              and state["ssm"].dtype == jnp.float32
              and state["conv"].dtype == jnp.dtype(cfg.dtype),
              "the slot state is not {conv, ssm float32} [ssm layers, slots]")
        s = srv.stats
        say(f"  ssm: {s['prefill_chunks']} slot-chunks, "
            f"{s['prefill_chunks_carried']} carried state; "
            f"{s['decode_ticks']} decode ticks, {s['moe_pairs_held']} of "
            f"{s['moe_pairs_routed']} routed pairs held, "
            f"{s['moe_experts_touched']} held experts touched")
        check(0 < s["moe_pairs_held"] < s["moe_pairs_routed"],
              "the share held every routed pair, or none")
        mem_line("ssm-serve")
    gc.collect()


# ==================================================================== train
def ce_loss(logits, labels):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    return -jnp.take_along_axis(logp, labels[:, 1:, None], -1).mean()


def run_steps(step, params, state, batch, P, watch, rng=()):
    losses = []
    mark = None
    for i in range(1, P["steps"] + 1):
        t0 = time.perf_counter()
        loss, params, state = step(params, state, batch, i, *rng)
        losses.append(float(loss))
        say(f"    step {i}: loss {losses[-1]:.4f} "
            f"({time.perf_counter() - t0:.2f}s)")
        if i == 2:
            mark = watch.mark()
    n, _ = watch.since(mark)
    check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(n == 0, f"{n} programs compiled after step 2")
    return params, state


def train_setup(P):
    """A fresh seeded model, its AdamW, and one seeded batch."""
    import paddle_tpu as pt
    model = build_model(P)
    opt = pt.optimizer.AdamW(learning_rate=P["lr"],
                             parameters=model.parameters())
    ids = np.random.default_rng(5).integers(
        0, P["cfg"].vocab_size, (P["train_b"], P["train_s"])).astype(np.int32)
    return model, opt, {"inputs": (ids,), "labels": (ids,)}


def phase_train(P, rehearse, watch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit import train_step_fn

    model, opt, batch = train_setup(P)
    init_fn, _ = opt.functional()
    params = model.raw_params()
    # f32 moments beside bf16 weights (bench.py's recipe)
    state = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                   init_fn(params))
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    step = train_step_fn(model, ce_loss, opt)
    say(f"  train: params {tree_bytes(params) / 2**30:.3f} GiB, optimizer "
        f"state {tree_bytes(state) / 2**30:.3f} GiB, batch "
        f"{P['train_b']}x{P['train_s']}")
    params, state = run_steps(step, params, state, batch, P, watch)
    # the program those steps ran, looked at again (the compile cache
    # hands it back)
    exe = step.lower(params, state, batch, 1).compile()
    mem = exe.memory_analysis()
    calls = exe.as_text().count("tpu_custom_call")
    want = 3 * P["cfg"].num_layers          # flash fwd + dq + dkv per layer
    say(f"    train program: tpu_custom_call={calls} (expected {want}) "
        f"args={mem.argument_size_in_bytes / 2**30:.3f} GiB "
        f"temp={mem.temp_size_in_bytes / 2**30:.3f} GiB "
        f"code={mem.generated_code_size_in_bytes / 2**20:.2f} MiB")
    if not rehearse:
        check(calls == want, f"train step holds {calls} Mosaic calls, "
                             f"expected {want}")
    mem_line("train")


# ==================================================================== mesh4
def phase_mesh4(P, phases, rehearse, watch):
    import jax
    from jax.sharding import Mesh

    import paddle_tpu.parallel as dist

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    phase_serve(P, phases, rehearse, watch, mesh=mesh, label="mesh4-serve")
    with phases.run("mesh4-train"):
        model, opt, batch = train_setup(P)
        hmesh = dist.init_mesh(dp=2, sharding=2, devices=jax.devices()[:4])
        with hmesh:
            step, params, state, _ = dist.parallel_train_step(
                model, ce_loss, opt, hmesh, zero_stage=1)
            run_steps(step, params, state, batch, P, watch,
                      rng=(jax.random.PRNGKey(0),))
        mem_line("mesh4-train")


# ===================================================================== main
def rehearse_kernels():
    """--rehearse only: answer "on a TPU?" with yes and run every
    pallas_call in the Mosaic interpreter (process-wide: the context-
    manager form is thread-local and would miss the serving thread) —
    the kernel code paths, without the chip."""
    from unittest import mock

    import jax
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import pallas as pallas_pack
    pallas_pack.on_tpu.cache_clear()
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        pallas_pack.on_tpu()
    pltpu.set_tpu_interpret_mode(pltpu.InterpretParams())


def main(argv=None):
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU, kernels interpreted; "
                         "proves the script, never the chip")
    args = ap.parse_args(argv)
    if args.rehearse:
        TAG = "REHEARSAL "
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={device['count']} jax={jax.__version__}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU. "
              f"Nothing was run (use --rehearse for the CPU dry run).",
              file=sys.stderr)
        return 4

    from paddle_tpu.device import enable_compile_cache
    cache_dir = enable_compile_cache()
    held = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({held} entries)")
    watch = CompileWatch()
    phases = Phases(watch)
    P = presets(args.rehearse)

    if args.rehearse:
        rehearse_kernels()

    t0 = time.perf_counter()
    with phases.run("kernels"):
        phase_kernels(P, args.rehearse)
    mem_line("kernels")
    phase_serve(P, phases, args.rehearse, watch)
    phase_hybrid(phases, args.rehearse, watch)
    phase_ssm(phases, args.rehearse, watch)
    with phases.run("train"):
        phase_train(P, args.rehearse, watch)
    gc.collect()
    if device["count"] >= 4:
        phase_mesh4(P, phases, args.rehearse, watch)
    else:
        say(f"phase mesh4: skipped: {device['count']} devices")

    say(f"compile cache: {cache_dir} ({held} entries before, "
        f"{cache_entries(cache_dir)} after; {watch.cache_hits} hits, "
        f"{watch.cache_misses} misses; {watch.count} executables, "
        f"{watch.seconds:.1f}s compiling) total "
        f"{time.perf_counter() - t0:.1f}s")
    ok = not phases.failed
    if not ok:
        say(f"chip_smoke: FAILED phases: {', '.join(phases.failed)}")
    say(json.dumps({"ok": ok, "device": device,
                    **({} if ok else {"failed": phases.failed})}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
