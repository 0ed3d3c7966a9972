"""Device-cost ledger, compile watch, and tick-phase attribution
(ISSUE 13).

Contracts under test:

- ``CostCatalog``: each (op, shape-signature) priced ONCE from the
  compiled program's own ``cost_analysis`` (exact FLOPs asserted for a
  known matmul), the catalog's executable is what dispatches (tokens
  bit-identical with the catalog on or off, greedy AND sampled), every
  dispatch charges, compiles are counted/timed, and a compile after
  warmup is flagged a RECOMPILE.
- server wiring: steady-state paged decode publishes nonzero
  ``server_flops_total{op}`` / ``server_hbm_bytes_total{op}`` and an
  MFU gauge; steady state stays ZERO-recompile across slot churn and
  admission waves (the shape-signature-leak guard); a forced new
  chunk width after warmup lands a ``compile`` recorder event with
  ``recompile=True`` and a ``compile_stall`` journey phase; tick
  phases publish and ride recorder tick events + postmortem bundles;
  ``/stats["costs"]`` and heartbeat-digest utilization.
- DISABLED catalog: treated exactly like None — zero clock reads and
  zero lock acquisitions on the tick path (FakeClock + counting-lock,
  the flight-recorder contract).
- skipped_page_dma cross-validation (PR-10 known cut): the goodput
  ledger's host-side DMA model tracks the COMPILED paged-attention
  program's bytes linearly in block-table width, with a documented
  constant factor.
- fleet merge: ``serving_mfu`` folds by MEAN, not sum.
- ``scripts/bench_track.py``: schema'd appends, the committed
  BENCHLOG/bands pass ``--check``, and an injected synthetic
  regression (or a malformed log line, or a missing banded metric)
  exits nonzero.

Everything but the cross-validation compiles runs on the StubModel
double — tier-1 fast."""
import importlib.util
import json
import os
import socket
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.inference.serving import serve_metrics
from paddle_tpu.telemetry import (CostCatalog, FakeClock, FlightRecorder,
                                  MetricRegistry, ServerTelemetry,
                                  merge_snapshots)
from paddle_tpu.telemetry.costs import TICK_PHASES
from paddle_tpu.telemetry.serving import TickBoundary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prompt(*toks):
    return np.asarray(toks, np.int32)


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _CountingLock:
    def __init__(self):
        self.acquisitions = 0

    def __enter__(self):
        self.acquisitions += 1
        return self

    def __exit__(self, *exc):
        return False


def _cat_with_peaks(**kw):
    """The CPU has no row in DEVICE_PEAKS; tests that read a
    utilisation inject peaks, as a deployment on an unlisted chip
    would."""
    return CostCatalog(peak_flops=1e12, peak_hbm_bytes_per_s=1e11, **kw)


def _paged_server(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 32)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 4)
    return ContinuousBatchingServer(StubModel(), **kw)


# --------------------------------------------------------------------------
# CostCatalog unit contracts
# --------------------------------------------------------------------------
class TestCostCatalogUnit:
    def test_program_prices_exact_flops_and_caches(self):
        cat = CostCatalog()
        fn = jax.jit(lambda a, b: jnp.dot(a, b))
        x = jnp.ones((64, 128), jnp.float32)
        y = jnp.ones((128, 32), jnp.float32)
        prog = cat.program("decode", fn, (x, y))
        assert prog.compiled_now and not prog.recompile
        # the compiler's own number: 2*M*N*K MACs for a plain matmul
        assert prog.flops == 2 * 64 * 32 * 128
        assert prog.hbm_bytes > 0
        out = prog(x, y)                    # dispatch == charge
        np.testing.assert_allclose(np.asarray(out), np.asarray(fn(x, y)))
        # same signature: cache hit, no second compile
        again = cat.program("decode", fn, (x, y))
        assert again is prog and not again.compiled_now
        assert cat.compiles() == {"decode": 1}
        # new signature: a second priced entry
        x2 = jnp.ones((32, 128), jnp.float32)
        prog2 = cat.program("decode", fn, (x2, y))
        assert prog2.compiled_now and prog2.flops == 2 * 32 * 32 * 128
        assert cat.compiles() == {"decode": 2}
        cat.flush_tick()
        tot = cat.totals()
        assert tot["decode"]["dispatches"] == 1
        assert tot["decode"]["flops"] == prog.flops

    def test_compile_metrics_published(self):
        reg = MetricRegistry()
        cat = CostCatalog(registry=reg)
        fn = jax.jit(lambda a: a + 1)
        prog = cat.program("prefill", fn, (jnp.ones((4,)),))
        prog(jnp.ones((4,)))
        cat.flush_tick()
        assert reg.get("server_compiles_total") \
            .labels(op="prefill").value == 1
        assert reg.get("serving_compile_seconds").count == 1
        assert reg.get("server_hbm_bytes_total") \
            .labels(op="prefill").value > 0

    def test_unpriceable_fn_falls_back_raw_not_a_compile(self):
        reg = MetricRegistry()
        cat = CostCatalog(registry=reg)
        # warm the catalog so a false recompile alarm WOULD fire
        fn = jax.jit(lambda a: a + 1)
        x = jnp.ones((4,))
        for _ in range(3):
            cat.program("decode", fn, (x,))(x)
            cat.flush_tick()
        assert cat.warmed

        def plain(x):                       # no .lower: not jitted
            return x * 2

        prog = cat.program("decode", plain, (jnp.ones((2,)),))
        assert cat.price_errors == 1
        assert prog.flops == 0.0 and prog.hbm_bytes == 0.0
        np.testing.assert_allclose(np.asarray(prog(jnp.ones((2,)))),
                                   [2.0, 2.0])
        # a pricing FAILURE is not an XLA compile: no compile counted,
        # no recompile/compile_stall alarm even after warmup
        assert not prog.compiled_now and not prog.recompile
        assert cat.recompiles == 0
        assert cat.compiles() == {"decode": 1}
        assert reg.get("server_compiles_total") \
            .labels(op="decode").value == 1

    def test_warmup_then_recompile_flagged(self):
        cat = CostCatalog(warm_after_ticks=2)
        fn = jax.jit(lambda a: a + 1)
        x = jnp.ones((4,))
        prog = cat.program("decode", fn, (x,))
        prog(x)
        cat.flush_tick()                    # compile tick: quiet resets
        assert not cat.warmed
        for _ in range(2):                  # two quiet charged ticks
            cat.program("decode", fn, (x,))(x)
            cat.flush_tick()
        assert cat.warmed and cat.recompiles == 0
        prog2 = cat.program("decode", fn, (jnp.ones((8,)),))
        assert prog2.compiled_now and prog2.recompile
        assert cat.recompiles == 1

    def test_warmup_is_per_op(self):
        """ISSUE 14 satellite (lifts the PR-12 global-warmup cut): each
        op warms independently, so the prefill program's legitimate new
        chunk-width signatures while ITS ladder is still climbing never
        fire a recompile alarm just because decode already warmed —
        and decode's shape-leak watch isn't reset by them either."""
        cat = CostCatalog(warm_after_ticks=2)
        fn = jax.jit(lambda a: a + 1)
        x = jnp.ones((4,))
        cat.program("decode", fn, (x,))(x)
        cat.flush_tick()
        for _ in range(2):                  # decode warms
            cat.program("decode", fn, (x,))(x)
            cat.flush_tick()
        assert cat.warmed_op("decode") and cat.warmed
        # a FIRST prefill compile after decode warmed: not a recompile
        y = jnp.ones((8,))
        p1 = cat.program("prefill", fn, (y,))
        p1(y)
        cat.flush_tick()
        assert p1.compiled_now and not p1.recompile
        assert cat.recompiles == 0
        assert not cat.warmed               # prefill still climbing
        # prefill climbs its pow2 ladder while unwarm: still no alarm,
        # and decode's armed watch is untouched by the churn
        z = jnp.ones((16,))
        p2 = cat.program("prefill", fn, (z,))
        p2(z)
        cat.flush_tick()
        assert not p2.recompile and cat.recompiles == 0
        assert cat.warmed_op("decode")
        for _ in range(2):                  # prefill warms too
            cat.program("prefill", fn, (z,))(z)
            cat.flush_tick()
        assert cat.warmed_op("prefill") and cat.warmed
        assert sorted(cat.snapshot()["warm_ops"]) == ["decode", "prefill"]
        # NOW a new prefill signature is a real recompile — and it trips
        # only prefill's alarm, not a decode one
        p3 = cat.program("prefill", fn, (jnp.ones((32,)),))
        assert p3.recompile and cat.recompiles == 1
        p4 = cat.program("decode", fn, (x,))
        assert not p4.compiled_now          # cache hit, no new alarm

    def test_mfu_exact_on_fake_clock(self):
        fc = FakeClock()
        reg = MetricRegistry()
        cat = CostCatalog(registry=reg, clock=fc, peak_flops=1000.0,
                          peak_hbm_bytes_per_s=100.0)
        fn = jax.jit(lambda a, b: jnp.dot(a, b))
        x = jnp.ones((4, 8), jnp.float32)
        y = jnp.ones((8, 2), jnp.float32)
        prog = cat.program("decode", fn, (x, y))     # 128 flops
        prog(x, y)
        tb = TickBoundary(cat, None, "decode_wait")  # the catalog alone
        fc.advance(0.5)
        tb.close()
        cat.flush_tick()
        # (128 flops / 0.5 s) / 1000 peak = 0.256
        assert cat.mfu() == pytest.approx(prog.flops / 0.5 / 1000.0)
        assert reg.get("serving_mfu").value == pytest.approx(cat.mfu())
        snap = cat.snapshot()
        assert snap["roofline_ratio"] >= snap["mfu"]
        assert snap["last_tick_phases"] == {"decode_wait": 0.5}
        # the phase histogram is the server telemetry's: a catalog
        # publishes none, whatever registry it was given
        assert reg.get("serving_tick_phase_seconds") is None

    def test_charge_bytes_is_flops_free(self):
        cat = CostCatalog()
        cat.charge_bytes("block_table", 4096)
        cat.charge_bytes("block_table", 4096)
        cat.flush_tick()
        tot = cat.totals()["block_table"]
        assert tot == {"flops": 0.0, "hbm_bytes": 8192.0,
                       "dispatches": 2}

    def test_bad_peaks_rejected(self):
        with pytest.raises(ValueError):
            CostCatalog(peak_flops=0)
        with pytest.raises(ValueError):
            CostCatalog(peak_hbm_bytes_per_s=-1)

    def test_device_without_peaks_row_publishes_no_utilisation(self):
        """The CPU has no row in DEVICE_PEAKS: the catalog still prices
        and charges, but computes no MFU and registers no gauge — a
        utilisation against a made-up peak is not published."""
        from paddle_tpu.telemetry.costs import DEVICE_PEAKS, device_peaks
        assert DEVICE_PEAKS["TPU v5 lite"] == (197e12, 819e9)
        with pytest.raises(LookupError, match="cpu"):
            device_peaks()
        with pytest.raises(LookupError):
            device_peaks("TPU v99")
        reg = MetricRegistry()
        cat = CostCatalog(registry=reg, clock=FakeClock())
        assert cat.peak_flops is None and cat.peak_hbm_bytes_per_s is None
        fn = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((8, 8))
        prog = cat.program("decode", fn, (x,))
        prog(x)
        cat.add_phase("decode_wait", 0.5)
        cat.flush_tick()
        assert cat.totals()["decode"]["flops"] > 0
        assert cat.mfu() is None
        assert cat.snapshot()["roofline_ratio"] is None
        assert reg.get("serving_mfu") is None


# --------------------------------------------------------------------------
# Program names: one constant name on the jit path and through the catalog
# --------------------------------------------------------------------------
class TestProgramNames:
    def test_hoisted_program_is_named_after_its_function_everywhere(self):
        """``hoisted_jit(f)`` is the module ``jit_<f's name>``: through
        ``.lower()``, through its compiled stage, and through the
        catalog's ahead-of-time executable, the same constant string
        (an id or a width in it would miss the persistent compile cache
        on every start)."""
        from paddle_tpu.jit.hoist import hoisted_jit
        w = jnp.ones((8, 8), jnp.float32)

        def decode_tick(x):
            return x @ w

        h = hoisted_jit(decode_tick)
        names = set()
        for width in (2, 4):
            x = jnp.ones((width, 8), jnp.float32)
            lowered = h.lower(x)
            assert "module @jit_decode_tick " in lowered.as_text()
            hlo = lowered.compile().as_text()
            names.add(hlo.split()[1].rstrip(","))
            prog = CostCatalog().program("decode", h, (x,))
            assert prog.compiled_now
            assert prog.executable.as_text().split()[1].rstrip(",") \
                == "jit_decode_tick"
            np.testing.assert_allclose(np.asarray(prog(x)),
                                       np.asarray(h(x)))
        assert names == {"jit_decode_tick"}

    @pytest.fixture(scope="class")
    def served_programs(self):
        """{op: module name} of what a paged server over the real
        llama_tiny bundle compiled through the catalog (the stub model
        brings program functions of its own)."""
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(3)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        cat = CostCatalog()
        srv = ContinuousBatchingServer(
            model, max_slots=2, max_cache_len=32, cache_backend="paged",
            page_size=8, costs=cat)
        srv.submit(_prompt(1, 2, 3), max_new_tokens=3)
        srv.run()
        return {op: p.executable.as_text().split()[1].rstrip(",")
                for op, p in cat.programs()}

    @pytest.mark.parametrize("op,module", [
        ("decode", "jit_decode_tick"), ("prefill", "jit_prefill_tick")])
    def test_serving_programs_carry_their_tick_names(
            self, served_programs, op, module):
        """The split tick's two programs as the cost catalog compiled
        them, which is what the server dispatches: the names the
        benchmark's ``decode_tick_ms`` and ``prefill_tick_ms`` look
        up in the device trace."""
        assert served_programs[op] == module
        assert "jit_run" not in served_programs.values()

    def test_dense_prefill_program_is_decode_step(self):
        """``_decode_bundle``'s jitted step (the dense prefill program,
        generation.py) is ``jit_decode_step``."""
        import paddle_tpu as pt
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        pt.seed(3)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        bundle = model._decode_bundle(16)
        init_caches, prefill_jit = bundle[0], bundle[4]
        x = jnp.zeros((1, 4, model.cfg.hidden_size), jnp.float32)
        text = prefill_jit.lower(x, init_caches(1), jnp.int32(0)).as_text()
        assert "module @jit_decode_step " in text


# --------------------------------------------------------------------------
# Disabled catalog: structurally zero cost (flight-recorder contract)
# --------------------------------------------------------------------------
class TestDisabledCatalog:
    def test_disabled_zero_clock_zero_locks_server_treats_as_none(self):
        fc = FakeClock()
        cat = CostCatalog(enabled=False, clock=fc)
        lock = _CountingLock()
        cat._lock = lock
        # program() on a disabled catalog is the identity — no AOT, no
        # clock
        fn = jax.jit(lambda a: a + 1)
        assert cat.program("decode", fn, (jnp.ones((2,)),)) is fn
        srv = _paged_server(costs=cat)
        assert srv._costs is None
        rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        out = srv.run()
        np.testing.assert_array_equal(out[rid],
                                      stub_tokens([1, 2, 3], 4))
        # telemetry=None, costs off: no tick ever built a boundary
        assert srv._boundary is None and srv._tick_seq == 0
        assert fc.reads == 0 and lock.acquisitions == 0
        assert cat._tick == {} and cat._phases == {}
        assert srv.device_costs() is None
        assert srv.utilization() == {}

    def test_costs_true_builds_on_server_clock_and_registry(self):
        tele = ServerTelemetry()
        srv = _paged_server(telemetry=tele, costs=True)
        assert srv._costs is not None
        assert srv._costs.clock is srv._clock
        rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=3)
        srv.run()
        assert tele.registry.get("server_flops_total") \
            .labels(op="decode").value > 0
        del rid


# --------------------------------------------------------------------------
# Server wiring: pricing, parity, steady state, recompiles, phases
# --------------------------------------------------------------------------
class TestServerCosting:
    def test_steady_state_publishes_nonzero_costs_and_mfu(self):
        tele = ServerTelemetry()
        cat = _cat_with_peaks(registry=tele.registry)
        srv = _paged_server(telemetry=tele, costs=cat)
        rng = np.random.default_rng(3)
        rids = []
        for _ in range(4):
            p = rng.integers(0, 16, (6,)).astype(np.int32)
            rids.append((srv.submit(p, max_new_tokens=6), p))
        outs = srv.run()
        for rid, p in rids:
            np.testing.assert_array_equal(outs[rid], stub_tokens(p, 6))
        flops = tele.registry.get("server_flops_total")
        hbm = tele.registry.get("server_hbm_bytes_total")
        assert flops.labels(op="decode").value > 0
        assert hbm.labels(op="decode").value > 0
        assert flops.labels(op="prefill").value > 0
        assert tele.registry.get("serving_mfu").value > 0
        snap = srv.device_costs()
        assert snap["ops"]["decode"]["dispatches"] > 0
        # every decode dispatch charged the same (single-signature)
        # compiled program: totals divide exactly
        dec = snap["ops"]["decode"]
        assert dec["flops"] % dec["dispatches"] == 0
        # transfers priced as bytes moved, zero FLOPs
        assert snap["ops"]["block_table"]["flops"] == 0
        assert snap["ops"]["block_table"]["hbm_bytes"] > 0
        assert snap["ops"]["state_push"]["hbm_bytes"] > 0
        util = srv.utilization()
        assert util["mfu"] == pytest.approx(cat.mfu())

    def test_tokens_bit_identical_with_and_without_catalog(self):
        for sample in (False, True):
            outs = []
            for costs in (None, True):
                srv = _paged_server(costs=costs, do_sample=sample,
                                    seed=11)
                rng = np.random.default_rng(7)
                rids = [srv.submit(rng.integers(0, 16, (5,))
                                   .astype(np.int32),
                                   max_new_tokens=7, seed=i)
                        for i in range(4)]
                got = srv.run()
                outs.append([got[r] for r in rids])
            for a, b in zip(*outs):
                np.testing.assert_array_equal(a, b)

    def test_steady_state_zero_recompiles_across_churn_and_waves(self):
        """The shape-signature-leak guard (ISSUE 13 satellite): after
        a warmup wave covers the workload's chunk widths, slot churn
        and admission waves must compile NOTHING new — a leak that
        reintroduced per-tick compiles fails here."""
        cat = CostCatalog()
        srv = _paged_server(costs=cat, prefill_tokens_per_tick=4,
                            max_slots=2)
        rng = np.random.default_rng(5)

        def wave():
            rids = []
            for _ in range(4):          # 4 requests through 2 slots:
                p = rng.integers(0, 16, (6,)).astype(np.int32)
                rids.append((srv.submit(p, max_new_tokens=5), p))
            outs = srv.run()
            for rid, p in rids:
                np.testing.assert_array_equal(outs[rid],
                                              stub_tokens(p, 5))

        wave()                          # warmup: compiles the ladder
        assert cat.warmed
        compiles = cat.compiles()
        for _ in range(3):              # churn waves, fresh prompts
            wave()
        assert cat.recompiles == 0
        assert cat.compiles() == compiles

    def test_recompile_lands_recorder_event_and_compile_stall(self):
        rec = FlightRecorder()
        cat = CostCatalog()
        srv = _paged_server(costs=cat, recorder=rec, journeys=True,
                            max_cache_len=64, page_size=4)
        # warm on short prompts (small chunk widths)
        for _ in range(2):
            rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
            srv.run()
        assert cat.warmed
        # a prompt wider than any warmed chunk width forces a fresh
        # ragged-prefill signature: a mid-serving RECOMPILE
        long_p = np.arange(17, dtype=np.int32) % 16
        rid = srv.submit(long_p, max_new_tokens=4)
        out = srv.run()
        np.testing.assert_array_equal(out[rid],
                                      stub_tokens(long_p, 4))
        assert cat.recompiles >= 1
        evs = [e for e in rec.events(kind="compile") if e["recompile"]]
        assert evs and evs[-1]["op"] == "prefill"
        assert evs[-1]["seconds"] >= 0
        timeline = srv.journey(rid)
        assert any(e.get("phase") == "compile_stall" for e in timeline)

    def test_phases_published_and_embedded_in_tick_events(self):
        tele = ServerTelemetry()
        rec = FlightRecorder()
        cat = CostCatalog(registry=tele.registry)
        srv = _paged_server(telemetry=tele, costs=cat, recorder=rec)
        rid = srv.submit(_prompt(2, 4, 6), max_new_tokens=6)
        srv.run()
        del rid
        snap = cat.snapshot()
        phases = snap["last_tick_phases"]
        assert phases and set(phases) <= set(TICK_PHASES)
        assert all(v >= 0 for v in phases.values())
        h = tele.registry.get("serving_tick_phase_seconds")
        assert h.labels(phase="decode_wait").count > 0
        assert h.labels(phase="admit").count > 0
        ticks = rec.events(kind="tick")
        assert ticks and "phases" in ticks[-1]
        assert set(ticks[-1]["phases"]) <= set(TICK_PHASES)

    def test_tick_phases_are_the_phases_a_tick_marks(self):
        """``TICK_PHASES`` lists what the tick loop marks, no more and
        no less: a paged server (ragged launches, an optimistic grow, a
        streamed callback) and a dense one (its admission's
        ``prefill_launch``) between them mark every phase, and mark
        nothing the tuple does not name."""
        marked = set()

        def served(**kw):
            cat = CostCatalog()
            add = cat.add_phase
            cat.add_phase = lambda phase, s: (marked.add(phase),
                                              add(phase, s))
            srv = ContinuousBatchingServer(StubModel(), max_slots=2,
                                           max_cache_len=32, costs=cat,
                                           **kw)
            for p in ((1, 2, 3), (4, 5, 6, 7, 8)):
                srv.submit(_prompt(*p), max_new_tokens=8,
                           on_token=lambda rid, toks: None)
            srv.run()
            return srv

        paged = served(cache_backend="paged", page_size=4,
                       admission="optimistic", headroom_pages=0)
        assert paged.stats["grow_pages"] > 0
        served()
        assert marked == set(TICK_PHASES)
        assert len(TICK_PHASES) == len(set(TICK_PHASES)) == 14

    def test_the_split_costs_one_clock_read_a_program(self):
        """The benchmark's untraced setting (the catalog alone): a tick
        reads the clock once a mark and nowhere else, so dividing a
        program's time where its call returns costs ONE read a decode
        tick and ONE a launch: a tick that launches and decodes makes
        16 reads (15 phases and the close; 14 before the split), a
        decode-only tick 11 (10 before). No span, no record, no
        warning: a sound tick compares each phase with the limit and
        goes on."""
        fc = FakeClock()
        cat = CostCatalog(clock=fc)
        srv = _paged_server(costs=cat)
        srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        srv.run()                          # compiles (the watch reads)
        srv.submit(_prompt(4, 5, 6), max_new_tokens=4)
        reads = []
        while True:
            before = fc.reads
            live = srv.step()
            reads.append(fc.reads - before)
            if not live:
                break
        assert reads[0] == 16              # admits, launches, decodes
        assert set(reads[1:-1]) == {11}    # decode ticks
        phases = cat.snapshot()["last_tick_phases"]
        assert set(phases) <= set(TICK_PHASES)
        assert not srv.slow_phases and srv.stats["slow_phases"] == 0

    def test_postmortem_freezes_costs_section(self):
        rec = FlightRecorder()
        srv = _paged_server(costs=True, recorder=rec)
        rid = srv.submit(_prompt(3, 1, 4), max_new_tokens=4)
        srv.run()
        del rid
        srv.kill()
        bundle = srv.postmortems()[-1]
        assert bundle["reason"] == "killed"
        costs = bundle["costs"]
        assert costs["ops"]["decode"]["flops"] > 0
        assert "last_tick_phases" in costs
        assert "compiles" in costs

    def test_stats_endpoint_carries_costs(self):
        tele = ServerTelemetry()
        srv = _paged_server(telemetry=tele, costs=True)
        rid = srv.submit(_prompt(1, 5, 2), max_new_tokens=3)
        srv.run()
        del rid
        ms = serve_metrics(srv)
        try:
            status, body = _get(ms.url + "/stats")
            assert status == 200
            stats = json.loads(body)["stats"]
            assert stats["costs"]["ops"]["decode"]["flops"] > 0
            assert "goodput" not in stats or True   # ledger-optional
        finally:
            ms.close()

    def test_heartbeat_digest_carries_utilization(self):
        from paddle_tpu.inference.remote import ReplicaHost
        srv = _paged_server(costs=_cat_with_peaks(), ledger=True)
        rid = srv.submit(_prompt(1, 2, 3), max_new_tokens=4)
        srv.run()
        del rid
        host = ReplicaHost(srv)          # not started: digest is pure
        d = host._digest()
        assert 0.0 <= d["util"]["goodput_ratio"] <= 1.0
        assert d["util"]["mfu"] > 0
        json.dumps(d)                    # digest must stay wire-safe


# --------------------------------------------------------------------------
# Heartbeat utilization over the real wire (loopback)
# --------------------------------------------------------------------------
def _loopback_available():
    try:
        s = socket.create_server(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


@pytest.mark.net
@pytest.mark.skipif(not _loopback_available(),
                    reason="cannot bind a loopback socket here")
class TestUtilizationOverWire:
    def test_remote_replica_reads_util_from_digest(self):
        from paddle_tpu.inference.remote import (RemoteReplica,
                                                 ReplicaHost)
        srv = _paged_server(costs=_cat_with_peaks(), ledger=True)
        host = ReplicaHost(srv, heartbeat_s=0.01).start()
        rep = RemoteReplica(host.address)
        try:
            rep.start()
            rid = rep.submit(_prompt(2, 5, 9), max_new_tokens=5)
            out = rep.wait(rid)
            np.testing.assert_array_equal(out,
                                          stub_tokens([2, 5, 9], 5))
            deadline = time.time() + 5.0
            util = {}
            while time.time() < deadline:
                util = rep.utilization()
                if util.get("mfu"):
                    break
                time.sleep(0.02)
            assert util.get("mfu", 0) > 0
            assert 0.0 <= util["goodput_ratio"] <= 1.0
        finally:
            rep.close()
            host.close()
            if srv._thread is not None:
                srv.stop(timeout=10)


# --------------------------------------------------------------------------
# skipped_page_dma cross-validation (PR-10 known cut closed)
# --------------------------------------------------------------------------
class TestSkippedDmaCrossValidation:
    """The goodput ledger's ``skipped_page_dma`` kind models the paged
    kernels' masked page traffic host-side as
    ``(table_width - live_pages) * page_size`` token-equivalents per
    live slot per launch. Here that model is held against the COMPILED
    programs' own ``cost_analysis`` bytes.

    Divergence, pinned: the compiled fallback touches each DMAed page
    a small CONSTANT number of times — gather materialization (write +
    read), the GQA head repeat, the QK^T and AV reads — plus
    [table-width]-sized f32 softmax intermediates, so compiled bytes
    per masked page = k x (page_size x kv-row bytes) with k a
    shape-dependent constant (~6 at llama-ish head dims, measured).
    The ledger counts each masked token ONCE. What the ledger needs —
    and what is asserted — is that the compiled cost is AFFINE in the
    table width (slopes agree across spans) with a per-page slope
    within a documented constant band of the model, so relative waste
    comparisons (the ROADMAP item-2 win condition) track the compiled
    programs."""

    S, NH, KVH, HD, PG, POOL = 4, 4, 2, 64, 16, 64

    def _decode_bytes(self, maxp):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        q = jnp.ones((self.S, self.NH, self.HD), jnp.float32)
        k = jnp.ones((self.POOL, self.PG, self.KVH, self.HD),
                     jnp.float32)
        v = jnp.ones_like(k)
        bt = jnp.zeros((self.S, maxp), jnp.int32)
        ln = jnp.full((self.S,), 5, jnp.int32)
        ca = jax.jit(paged_attention).lower(
            q, k, v, bt, ln).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca["bytes accessed"])

    def test_decode_model_tracks_compiled_bytes(self):
        b8, b16, b32 = (self._decode_bytes(p) for p in (8, 16, 32))
        # affine in table width: per-page slope stable across spans
        slope_a = (b16 - b8) / (16 - 8)
        slope_b = (b32 - b16) / (32 - 16)
        assert slope_a > 0
        assert abs(slope_a - slope_b) / slope_b < 0.25
        # the model's bytes for one masked page, per slot
        row_bytes = 2 * self.KVH * self.HD * 4          # K+V, f32
        model_page = self.PG * row_bytes
        ratio = (slope_b / self.S) / model_page
        # documented constant band (see class docstring): the program
        # touches each page ~4-8x; way outside means the model or the
        # kernel's traffic shape changed — re-derive, don't ignore
        assert 2.0 <= ratio <= 12.0, \
            f"compiled-vs-model bytes ratio {ratio:.2f} left [2, 12]"

    def test_ragged_prefill_bytes_scale_with_table_width(self):
        from paddle_tpu.ops.pallas.ragged_prefill import \
            ragged_prefill_attention

        def bytes_at(maxp):
            q = jnp.ones((self.S, 2, self.NH, self.HD), jnp.float32)
            k = jnp.ones((self.POOL, self.PG, self.KVH, self.HD),
                         jnp.float32)
            v = jnp.ones_like(k)
            bt = jnp.zeros((self.S, maxp), jnp.int32)
            t0 = jnp.zeros((self.S,), jnp.int32)
            ca = jax.jit(ragged_prefill_attention).lower(
                q, k, v, bt, t0).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return float(ca["bytes accessed"])

        b8, b32 = bytes_at(8), bytes_at(32)
        slope = (b32 - b8) / (32 - 8) / self.S
        row_bytes = 2 * self.KVH * self.HD * 4
        ratio = slope / (self.PG * row_bytes)
        # the ragged kernel shares the decode fallback's gather
        # structure but reads the gathered frame once per chunk row —
        # wider band, same linear-tracking property
        assert 1.0 <= ratio <= 25.0, \
            f"ragged compiled-vs-model ratio {ratio:.2f} left [1, 25]"


# --------------------------------------------------------------------------
# Fleet merge: serving_mfu folds by MEAN
# --------------------------------------------------------------------------
class TestMfuFleetMerge:
    def test_mfu_merges_by_mean_not_sum(self):
        snaps = []
        for mfu, slots in ((0.4, 3), (0.8, 5)):
            reg = MetricRegistry()
            reg.gauge("serving_mfu", "").set(mfu)
            reg.gauge("serving_active_slots", "").set(slots)
            snaps.append(reg.snapshot())
        merged = merge_snapshots(snaps)
        assert merged["serving_mfu"]["samples"][()] == \
            pytest.approx(0.6)
        # control: ordinary gauges still SUM
        assert merged["serving_active_slots"]["samples"][()] == 8


# --------------------------------------------------------------------------
# bench_track: schema, append, and the regression gate
# --------------------------------------------------------------------------
class TestBenchTrack:
    def test_validate_rejects_bad_rounds(self):
        bt = _load_script("bench_track")
        ok = bt.validate_round({"metric": "m_1", "value": 1.5,
                                "unit": "tok/s"})
        assert ok["ts"]                       # auto-stamped
        for bad in (
                {"value": 1, "unit": "x"},                   # no metric
                {"metric": "m", "unit": "x"},                # no value
                {"metric": "m", "value": 1},                 # no unit
                {"metric": "bad-name", "value": 1, "unit": "x"},
                {"metric": "tokéns", "value": 1, "unit": "x"},
                {"metric": "m", "value": float("nan"), "unit": "x"},
                {"metric": "m", "value": True, "unit": "x"},
                {"metric": "m", "value": 1, "unit": "x",
                 "surprise": 1},                             # unknown
                {"metric": "m", "value": 1, "unit": "x",
                 "vs_baseline": float("inf")},
        ):
            with pytest.raises(bt.BenchLogError):
                bt.validate_round(bad)

    def test_append_and_load_round_trip(self, tmp_path):
        bt = _load_script("bench_track")
        log = str(tmp_path / "log.jsonl")
        bt.append_round({"metric": "m_a", "value": 2.0, "unit": "x",
                         "note": "n"}, path=log)
        bt.append_round({"metric": "m_a", "value": 3.0, "unit": "x"},
                        path=log)
        rounds = bt.load_rounds(log)
        assert [r["value"] for r in rounds] == [2.0, 3.0]

    def test_committed_log_passes_committed_bands(self):
        bt = _load_script("bench_track")
        ok, report = bt.check()
        assert ok, "\n".join(report)
        assert any("paged_decode_flops_per_token" in line
                   for line in report)

    def test_synthetic_regression_exits_nonzero(self, tmp_path):
        bt = _load_script("bench_track")
        log = str(tmp_path / "log.jsonl")
        bands = str(tmp_path / "bands.json")
        bt.append_round({"metric": "paged_decode_mfu", "value": 0.02,
                         "unit": "ratio"}, path=log)
        with open(bands, "w") as f:
            json.dump({"paged_decode_mfu": {"min": 0.01}}, f)
        assert bt.main(["check", "--log", log, "--bands", bands]) == 0
        # the regression round lands LAST — latest wins, gate trips
        bt.append_round({"metric": "paged_decode_mfu", "value": 0.001,
                         "unit": "ratio"}, path=log)
        assert bt.main(["--check", "--log", log, "--bands", bands]) == 1

    def test_missing_banded_metric_fails(self, tmp_path):
        bt = _load_script("bench_track")
        log = str(tmp_path / "log.jsonl")
        bands = str(tmp_path / "bands.json")
        bt.append_round({"metric": "other", "value": 1.0, "unit": "x"},
                        path=log)
        with open(bands, "w") as f:
            json.dump({"never_recorded": {"min": 0}}, f)
        ok, report = bt.check(log_path=log, bands_path=bands)
        assert not ok and "never_recorded" in report[0]

    def test_malformed_log_line_fails_loudly(self, tmp_path):
        bt = _load_script("bench_track")
        log = str(tmp_path / "log.jsonl")
        with open(log, "w") as f:
            f.write('{"metric": "m", "value": 1.0, "unit": "x", '
                    '"ts": "t"}\n')
            f.write("not json at all\n")
        with pytest.raises(bt.BenchLogError):
            bt.load_rounds(log)
        ok, report = bt.check(log_path=log,
                              bands_path=os.path.join(
                                  REPO, "scripts", "bench_bands.json"))
        assert not ok and "FAIL" in report[0]
