"""Telemetry subsystem (paddle_tpu/telemetry): metric registry, trace
spans, Prometheus exposition, and the serving SLO instrumentation —
everything on a FAKE clock so TTFT/TPOT/queue-wait assertions are exact
(no sleeps, no wall-time flake)."""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.telemetry import (FakeClock, MetricRegistry, MetricsServer,
                                  NULL_INSTRUMENT, NULL_SPAN,
                                  ServerTelemetry, Tracer,
                                  parse_prometheus, render_prometheus)


def _model():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(21)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _scripted_telemetry():
    fc = FakeClock()
    reg = MetricRegistry()
    return ServerTelemetry(registry=reg, clock=fc,
                           tracer=Tracer(clock=fc)), fc, reg


def _record_annotations(monkeypatch):
    """Stand a recorder in for ``jax.profiler.TraceAnnotation`` (a
    tracer looks it up when it is built): returns the list it appends
    ("enter", name, kwargs) and ("exit", name) to."""
    import jax
    seen = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            seen.append(("enter", self.name, self.kwargs))
            return self

        def __exit__(self, *exc):
            seen.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return seen


def _hist(reg, name, labels=None):
    m = reg.get(name)
    child = m.labels(**labels) if labels else m
    return child.count, child.sum


# ------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricRegistry()
        c = reg.counter("c_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(7)
        g.inc(3)
        g.dec(1)
        assert g.value == 9.0
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.1, 0.5, 5.0):     # le is INCLUSIVE: 0.1 -> le=0.1
            h.observe(v)
        snap = h.samples()[()]
        assert snap["buckets"] == [(0.1, 2), (1.0, 3), ("+Inf", 4)]
        assert snap["count"] == 4 and snap["sum"] == pytest.approx(5.65)

    def test_labels(self):
        reg = MetricRegistry()
        c = reg.counter("req_total", labelnames=("state",))
        c.labels(state="ok").inc(2)
        c.labels(state="err").inc()
        assert c.labels(state="ok").value == 2.0
        with pytest.raises(ValueError, match="expected labels"):
            c.labels(wrong="x")
        with pytest.raises(ValueError, match="bind them"):
            c.inc()          # labeled metric needs .labels() first

    def test_idempotent_and_conflicting_registration(self):
        reg = MetricRegistry()
        a = reg.counter("x_total", labelnames=("k",))
        assert reg.counter("x_total", labelnames=("k",)) is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("x_total", labelnames=("other",))

    def test_thread_safety_exact_totals(self):
        import threading
        reg = MetricRegistry()
        c = reg.counter("n_total")
        h = reg.histogram("v", buckets=(10.0,))

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(1.0)

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000.0
        assert h.count == 8000 and h.sum == pytest.approx(8000.0)


class TestDisabledRegistry:
    def test_null_instruments_shared_and_free(self):
        reg = MetricRegistry(enabled=False)
        c = reg.counter("a_total")
        assert c is NULL_INSTRUMENT
        assert c.labels(anything="x") is NULL_INSTRUMENT
        c.inc()
        reg.gauge("g").set(5)
        reg.histogram("h").observe(1.0)
        assert reg.snapshot() == {}
        assert render_prometheus(reg) == "\n"

    def test_disabled_tracer_reads_no_clock(self):
        fc = FakeClock()
        tr = Tracer(clock=fc, enabled=False)
        with tr.span("x", k=1):
            pass
        tr.instant("y")
        assert tr.span("z") is NULL_SPAN
        assert fc.reads == 0 and tr.events() == []

    def test_disabled_server_telemetry_reads_no_clock(self):
        """The SLO layer's contract: with a disabled registry every
        lifecycle hook is a no-op — zero clock reads, zero samples."""
        fc = FakeClock()
        tele = ServerTelemetry(registry=MetricRegistry(enabled=False),
                               clock=fc)
        tele.on_submit(0, 8, 1)
        tele.on_admit(0, 0)
        tele.on_first_token(0, 8, 0)
        tele.on_tick(0.5, 1, 1)
        tele.on_prefill_batch(0.5, width=8)
        tele.on_finish(0, 4)
        tele.set_pool(1, 2, 3)
        tele.add_null_writes(5)
        assert fc.reads == 0
        assert tele.registry.snapshot() == {}
        assert tele.tracer.events() == []

    def test_server_with_disabled_telemetry_skips_hot_path(self):
        tele = ServerTelemetry(registry=MetricRegistry(enabled=False),
                               clock=FakeClock())
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32, telemetry=tele)
        assert srv._tele is None            # single attr check per call
        rid = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        assert len(srv.run()[rid]) == 3
        assert tele.clock.reads == 0


# -------------------------------------------------------------- tracing

class TestTracing:
    def test_span_timing_and_args(self):
        fc = FakeClock()
        tr = Tracer(clock=fc)
        with tr.span("prefill", tokens=128) as sp:
            fc.advance(0.5)
            sp.set(chunks=2)
        (ev,) = tr.events()
        assert ev["name"] == "prefill" and ev["ph"] == "X"
        assert ev["ts"] == 0.0 and ev["dur"] == pytest.approx(5e5)
        assert ev["args"] == {"tokens": 128, "chunks": 2}

    def test_cross_scope_span_and_with_span(self, tmp_path):
        fc = FakeClock()
        tr = Tracer(clock=fc)
        sp = tr.begin_span("queued", rid=1)      # ends on another path
        fc.advance(2.0)

        def work():
            with tr.span("work"):
                fc.advance(1.0)
                return 42

        assert work() == 42
        sp.end()
        sp.end()                                  # double end: no-op
        names = {e["name"]: e for e in tr.events()}
        assert names["work"]["dur"] == pytest.approx(1e6)
        assert names["queued"]["dur"] == pytest.approx(3e6)
        out = tmp_path / "trace.json"
        assert tr.export_chrome_trace(str(out)) == 2
        data = json.loads(out.read_text())
        assert {e["name"] for e in data["traceEvents"]} == {"queued",
                                                            "work"}

    def test_max_events_bounds_memory(self):
        tr = Tracer(clock=FakeClock(), max_events=2)
        for _ in range(4):
            with tr.span("s"):
                pass
        assert len(tr.events()) == 2 and tr.dropped == 2

    def test_same_thread_spans_reach_the_profiler_request_spans_never(
            self, monkeypatch):
        """A span its opening thread closes (``with``, or the tick's
        phase boundary) is mirrored into a
        ``jax.profiler.TraceAnnotation``; a ``begin_span`` (the
        ``request.*`` spans, which may end on another thread) never
        is. Collection is the same for both."""
        seen = _record_annotations(monkeypatch)
        tr = Tracer(clock=FakeClock())
        with tr.span("serve.decode_wait", tick=3, rids=[4, 5]):
            assert seen == [("enter", "serve.decode_wait",
                             {"tick": 3, "rids": "4 5"})]
        tr.begin_span("request.queued", rid=4).end()
        assert seen == [("enter", "serve.decode_wait",
                         {"tick": 3, "rids": "4 5"}),
                        ("exit", "serve.decode_wait")]
        names = [e["name"] for e in tr.events()]
        assert names == ["serve.decode_wait", "request.queued"]
        assert tr.events()[0]["args"] == {"tick": 3, "rids": [4, 5]}


# ------------------------------------------------- the tick's boundary

class _SteppingClock(FakeClock):
    """A fake clock on which every read takes a millisecond, so that no
    two reads agree and every phase has a length."""

    __slots__ = ()

    def now(self):
        t = super().now()
        self.advance(0.001)
        return t


# which side of the split a phase of the split tick falls on: a
# program's own (the host enqueueing it, then waiting for its value)
# against the rest, through which the chip is idle (the host's doing)
_CHIP_PHASES = {"prefill_dispatch", "prefill_wait", "decode_dispatch",
                "decode_wait"}
_HOST_PHASES = {"expire", "admit", "prefill_pack", "activate", "grow",
                "state_push", "emit", "harvest", "callbacks"}


def _stub_server(**kw):
    from _serving_stub import StubModel
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_cache_len", 32)
    kw.setdefault("cache_backend", "paged")
    kw.setdefault("page_size", 4)
    return ContinuousBatchingServer(StubModel(), **kw)


def _serve_spans(tele, tick=None):
    return [e for e in tele.tracer.events()
            if e["name"].startswith("serve.")
            and (tick is None or e["args"].get("tick") == tick)]


class TestTickBoundary:
    @pytest.mark.parametrize("consumers", ["catalog", "telemetry", "both"])
    def test_one_mark_is_one_read_whichever_consumers_are_on(
            self, consumers):
        from paddle_tpu.telemetry import CostCatalog
        from paddle_tpu.telemetry.serving import TickBoundary
        fc = FakeClock()
        cat = CostCatalog(clock=fc) if consumers != "telemetry" else None
        tele = ServerTelemetry(clock=fc) if consumers != "catalog" \
            else None
        tb = TickBoundary(cat, tele, "expire", tick=7)
        assert fc.reads == 1                       # opening reads once
        for n, phase in enumerate(("admit", "decode_dispatch",
                                   "decode_wait", "emit"), 2):
            fc.advance(0.125)
            tb.mark(phase)
            assert fc.reads == n
        fc.advance(0.125)
        tb.close()
        assert fc.reads == 6
        want = {"expire": 0.125, "admit": 0.125, "decode_dispatch": 0.125,
                "decode_wait": 0.125, "emit": 0.125}
        if cat is not None:
            assert cat.pending_phases() == want
        if tele is not None:
            h = tele.registry.get("serving_tick_phase_seconds")
            assert {k[0]: v["sum"] for k, v in h.samples().items()} \
                == want
            spans = _serve_spans(tele, tick=7)
            assert [e["name"] for e in spans] == [
                "serve.expire", "serve.admit", "serve.decode_dispatch",
                "serve.decode_wait", "serve.emit"]
            # contiguous: each ends where the next begins
            for a, b in zip(spans, spans[1:]):
                assert a["ts"] + a["dur"] == pytest.approx(b["ts"])

    def test_scripted_tick_phases_sum_to_its_wall_and_split_host_from_chip(
            self):
        """One tick that admits, prefills, activates and decodes: its
        serve.* spans tile the tick's wall with no hole, the programs
        are dispatched inside the *_wait phases and the host's work
        falls in the phases that leave the chip idle. A program's time
        divides where its call returns: ``*_dispatch`` then ``*_wait``."""
        clock = _SteppingClock()
        tele = ServerTelemetry(clock=clock)
        srv = _stub_server(telemetry=tele)
        at = {}

        def spy(name, attr):
            inner = getattr(srv, attr)

            def wrapped(*a, **kw):
                at.setdefault(name, []).append(srv._boundary.phase)
                return inner(*a, **kw)
            setattr(srv, attr, wrapped)

        for name in ("_expire_locked", "_admit_ragged", "_ragged_fn",
                     "_flush_slot_state", "_harvest"):
            spy(name, name)
        srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
        srv._decode_jit = srv._build_decode_step()
        spy("decode", "_decode_jit")
        srv.step()

        assert at == {"_expire_locked": ["expire"],
                      "_admit_ragged": ["admit", "admit"],
                      "_ragged_fn": ["prefill_dispatch"],
                      "_harvest": ["harvest", "harvest"],
                      "_flush_slot_state": ["state_push"],
                      "decode": ["decode_dispatch"]}
        spans = _serve_spans(tele, tick=1)
        names = [e["name"][len("serve."):] for e in spans]
        assert names == ["expire", "admit", "prefill_pack",
                         "prefill_dispatch", "prefill_wait", "activate",
                         "admit", "harvest", "state_push",
                         "decode_dispatch", "decode_wait", "emit",
                         "harvest", "admit", "callbacks"]
        assert set(names) <= _CHIP_PHASES | _HOST_PHASES
        wall = spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"]
        assert sum(e["dur"] for e in spans) == pytest.approx(wall)
        assert all(e["dur"] > 0 for e in spans)
        h = tele.registry.get("serving_tick_phase_seconds")
        by_phase = {k[0]: v["sum"] for k, v in h.samples().items()}
        assert sum(by_phase.values()) * 1e6 == pytest.approx(wall)
        # the launch's span says what it served, and the request's own
        # prefill span carries the same tick
        launch = spans[names.index("prefill_dispatch")]["args"]
        assert launch == {"tick": 1, "width": 4, "rows": 1,
                          "launch_rows": 2 * 4, "rids": [0]}
        # the wait carries the same, and that the host blocked on it
        assert spans[names.index("prefill_wait")]["args"] == dict(
            launch, blocked=1)
        (prefill,) = [e for e in tele.tracer.events()
                      if e["name"] == "request.prefill"]
        assert prefill["args"]["tick"] == 1 and prefill["args"]["rid"] == 0
        assert tele.registry.get("serving_prefill_launches_total") \
            .labels(width=4).value == 1.0
        # the tick and prefill-batch histograms and the stat are fed
        # from the boundary's reads: the spans' own lengths
        dur = {n: e["dur"] / 1e6 for n, e in zip(names, spans)}
        assert _hist(tele.registry, "serving_tick_seconds") == \
            (1, pytest.approx(dur["decode_dispatch"] + dur["decode_wait"]))
        batch = dur["prefill_dispatch"] + dur["prefill_wait"] \
            + dur["activate"]
        assert _hist(tele.registry, "serving_prefill_seconds") == \
            (1, pytest.approx(batch))
        assert srv.stats["prefill_wall_s"] == pytest.approx(batch)

    def test_serve_spans_are_mirrored_request_spans_are_not(
            self, monkeypatch):
        seen = _record_annotations(monkeypatch)
        tele = ServerTelemetry(clock=FakeClock())
        srv = _stub_server(telemetry=tele)
        srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=3)
        srv.run()
        entered = [name for kind, name, *_ in seen if kind == "enter"]
        exited = [name for kind, name, *_ in seen if kind == "exit"]
        assert entered and sorted(entered) == sorted(exited)
        assert all(n.startswith("serve.") for n in entered)
        assert {"serve.submit", "serve.prefill_wait",
                "serve.decode_wait", "serve.callbacks"} <= set(entered)
        collected = {e["name"] for e in tele.tracer.events()}
        assert {"request.queued", "request.prefill",
                "request.decode"} <= collected

    def test_off_means_no_clock_read_and_no_annotation(self, monkeypatch):
        """telemetry=None, costs=None: a tick builds no boundary, reads
        no clock and builds no TraceAnnotation, and submit() takes the
        bare lock."""
        seen = _record_annotations(monkeypatch)
        fc = FakeClock()
        srv = _stub_server(clock=fc)
        rid = srv.submit(np.asarray([1, 2, 3], np.int32),
                         max_new_tokens=4)
        assert len(srv.run()[rid]) == 4
        assert fc.reads == 0 and seen == []
        assert srv._boundary is None and srv._tick_seq == 0
        assert srv.stats["prefill_wall_s"] == 0.0

    def test_untraced_benchmark_setting_reads_only_at_the_boundary(
            self, monkeypatch):
        """telemetry=None with the catalog on (the benchmark's untraced
        runs): once the programs are compiled, every clock read of a
        wave is one of the boundary's, and it feeds the catalog alone:
        no span, no TraceAnnotation."""
        from paddle_tpu.telemetry import CostCatalog
        from paddle_tpu.telemetry.serving import TickBoundary
        seen = _record_annotations(monkeypatch)
        fc = FakeClock()
        cat = CostCatalog(clock=fc)
        srv = _stub_server(costs=cat)
        prompt = np.asarray([1, 2, 3], np.int32)
        srv.submit(prompt, max_new_tokens=4)
        srv.run()                          # compiles (the watch reads)
        marks = []
        for attr in ("mark", "close"):
            inner = getattr(TickBoundary, attr)

            def counted(self, *a, _inner=inner, **kw):
                marks.append(1)
                return _inner(self, *a, **kw)
            monkeypatch.setattr(TickBoundary, attr, counted)
        before = fc.reads
        srv.submit(prompt, max_new_tokens=4)
        srv.run()
        assert marks and fc.reads - before == len(marks)
        assert seen == []
        assert set(cat.snapshot()["last_tick_phases"]) <= \
            _CHIP_PHASES | _HOST_PHASES

    def test_submit_lock_wait_is_observed_and_rides_the_queued_span(self):
        clock = _SteppingClock()
        tele = ServerTelemetry(clock=clock)
        srv = _stub_server(telemetry=tele)
        for _ in range(3):
            srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
        # two reads around the acquisition, a millisecond apart here
        n, total = _hist(tele.registry, "serving_submit_lock_wait_seconds")
        assert n == 3 and total == pytest.approx(0.003)
        srv.run()
        queued = [e for e in tele.tracer.events()
                  if e["name"] == "request.queued"]
        assert len(queued) == 3
        assert all(e["args"]["lock_wait_s"] == pytest.approx(0.001)
                   for e in queued)

    def test_idle_serve_loop_waits_in_idle_wait(self):
        """With nothing to do the serve thread's sleep is a phase of
        its own, with no tick number, and is nobody's fault: readers
        leave it out."""
        import time
        tele = ServerTelemetry()
        srv = _stub_server(telemetry=tele)
        srv.start(idle_sleep=0.001)
        try:
            deadline = time.monotonic() + 10.0
            h = tele.registry.get("serving_tick_phase_seconds")
            while h.labels(phase="idle_wait").count < 3 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
        finally:
            srv.stop()
        assert h.labels(phase="idle_wait").count >= 3
        idle = [e for e in tele.tracer.events()
                if e["name"] == "serve.idle_wait"]
        assert idle and all("tick" not in e.get("args", {}) for e in idle)


# ------------------------------- the time around the chip's programs

class _SlowValue:
    """Stands in for a decode tick's tokens on the device: reading it
    back (``np.asarray``) is where the host blocks, and ``on_read``
    runs there, inside ``decode_wait``."""

    def __init__(self, value, on_read):
        self.value, self.on_read = value, on_read

    def __array__(self, dtype=None, copy=None):
        self.on_read()
        return np.asarray(self.value)


def _stall_next_decode_wait(srv, on_read):
    """Make the next decode tick's read-back run ``on_read`` first."""
    if srv._decode_jit is None:
        srv._decode_jit = srv._build_decode_step()
    attr = "_decode_jit"
    if srv._costs is not None:
        attr = "_decode_prog"
        if srv._decode_prog is None:
            srv._decode_prog = srv._cost_program(
                srv._cost_op("decode"), srv._decode_jit,
                (srv._tok, srv._caches, srv._t, srv._keys,
                 srv._push_idle))
    inner = getattr(srv, attr)

    def once(*a):
        setattr(srv, attr, inner)
        *state, toks = inner(*a)
        return (*state, _SlowValue(toks, on_read))
    setattr(srv, attr, once)


_SERVER_LOG = "paddle_tpu.inference.continuous_batching"


class TestTickSplit:
    def test_first_token_delivery_is_a_span_of_its_request(self):
        """``request.deliver`` runs from the draw to the return of the
        request's first callback, once, with ``rid`` and ``tick``; the
        request's spans tile its life and share ``rid``."""
        clock = _SteppingClock()
        tele = ServerTelemetry(clock=clock)
        srv = _stub_server(telemetry=tele)
        got = []
        rid = srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4,
                         on_token=lambda r, toks: got.append(len(toks)))
        quiet = srv.submit(np.asarray([4, 5], np.int32), max_new_tokens=4)
        srv.run()
        assert sum(got) == 4 and len(got) > 1     # several callbacks
        by_name = {}
        for e in tele.tracer.events():
            if e["name"].startswith("request.") \
                    and e["args"].get("rid") == rid:
                by_name.setdefault(e["name"], []).append(e)
        assert {k: len(v) for k, v in by_name.items()} == {
            "request.queued": 1, "request.prefill": 1,
            "request.deliver": 1, "request.decode": 1}
        order = [by_name["request." + n][0]
                 for n in ("queued", "prefill", "deliver", "decode")]
        deliver = order[2]
        # the launch that drew the token and the span agree on the tick
        assert deliver["args"] == {"rid": rid, "tick": 1}
        assert order[1]["args"]["tick"] == 1
        (launch,) = [e for e in _serve_spans(tele, tick=1)
                     if e["name"] == "serve.prefill_wait"]
        assert rid in launch["args"]["rids"]
        # each span ends at the read that opens the next: no hole
        for a, b in zip(order, order[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
        # the turn that drew it ends after its launch: the callback
        # returns before the next turn dispatches the decode it owes
        assert "serve.decode_dispatch" not in {
            e["name"] for e in _serve_spans(tele, tick=1)}
        spans = {e["name"]: e for e in _serve_spans(tele, tick=2)}
        assert deliver["ts"] + deliver["dur"] <= \
            spans["serve.decode_dispatch"]["ts"]
        n, total = _hist(tele.registry,
                         "serving_first_token_delivery_seconds")
        assert n == 1 and total == pytest.approx(deliver["dur"] / 1e6)
        assert tele.undelivered == {}
        # a request that streams nothing has no delivery to time
        assert not [e for e in tele.tracer.events()
                    if e["name"] == "request.deliver"
                    and e["args"]["rid"] == quiet]

    def test_a_request_that_ends_with_its_first_token_is_delivered(self):
        tele = ServerTelemetry(clock=_SteppingClock())
        srv = _stub_server(telemetry=tele)
        got = []
        rid = srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=1,
                         on_token=lambda r, toks: got.append(list(toks)))
        srv.run()
        assert len(got) == 1 and len(got[0]) == 1
        names = [e["name"] for e in tele.tracer.events()
                 if e["name"].startswith("request.")
                 and e["args"].get("rid") == rid]
        assert names.count("request.deliver") == 1
        assert "request.decode" not in names      # finished before
        assert _hist(tele.registry,
                     "serving_first_token_delivery_seconds")[0] == 1
        assert tele.undelivered == {}

    def test_a_replayed_first_token_is_not_delivered_again(self):
        tele, fc, reg = _scripted_telemetry()
        tele.on_submit(7, 3, 1)
        tele.on_admit(7, 0)
        tele.on_first_token(7, 3, 0, streams=True)
        fc.advance(0.25)
        tele.on_first_delivery(7)
        tele.on_preempt(7, 1)
        tele.on_admit(7, 0)
        tele.on_first_token(7, 3, 0, streams=True)    # the replay's
        assert tele.undelivered == {}
        assert _hist(reg, "serving_first_token_delivery_seconds") == \
            (1, pytest.approx(0.25))
        names = [e["name"] for e in tele.tracer.events()]
        assert names.count("request.deliver") == 1

    def test_a_cancelled_request_drops_its_delivery(self):
        tele, fc, reg = _scripted_telemetry()
        tele.on_submit(3, 3, 1)
        tele.on_admit(3, 0)
        tele.on_first_token(3, 3, 0, streams=True)
        tele.on_cancel(3)
        assert tele.undelivered == {}
        (ev,) = [e for e in tele.tracer.events()
                 if e["name"] == "request.deliver"]
        assert ev["args"]["canceled"] is True
        assert _hist(reg, "serving_first_token_delivery_seconds")[0] == 0

    def test_a_launch_that_completes_no_prompt_blocks_nothing(self):
        """A prompt longer than the per-tick budget takes two launches:
        the first reads nothing back (``blocked=0``) and adds nothing
        to ``prefill_wall_s`` / ``serving_prefill_seconds``; both
        count as launches."""
        clock = _SteppingClock()
        tele = ServerTelemetry(clock=clock)
        srv = _stub_server(telemetry=tele, prefill_tokens_per_tick=4)
        srv.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=2)
        srv.run()
        waits = [e for e in _serve_spans(tele)
                 if e["name"] == "serve.prefill_wait"]
        assert [e["args"]["blocked"] for e in waits] == [0, 1]
        launches = tele.registry.get("serving_prefill_launches_total")
        assert sum(launches.samples().values()) == 2
        # the one that blocked: its dispatch, wait and activation
        tick = waits[1]["args"]["tick"]
        dur = {e["name"]: e["dur"] / 1e6
               for e in _serve_spans(tele, tick=tick)}
        batch = dur["serve.prefill_dispatch"] + dur["serve.prefill_wait"] \
            + dur["serve.activate"]
        assert _hist(tele.registry, "serving_prefill_seconds") == \
            (1, pytest.approx(batch))
        assert srv.stats["prefill_wall_s"] == pytest.approx(batch)
        # the first launch's tick still tiles: its wait closes at "admit"
        first = _serve_spans(tele, tick=waits[0]["args"]["tick"])
        names = [e["name"][len("serve."):] for e in first]
        at = names.index("prefill_wait")
        assert names[at - 1:at + 2] == ["prefill_dispatch", "prefill_wait",
                                        "admit"]
        assert "activate" not in names

    def test_a_stalled_phase_names_itself(self, caplog):
        """A scripted 5 s ``decode_wait`` in which a compile ended and
        the collector paused: ONE record with the phase, the tick, its
        seconds and both causes, the two stats bumped, the counter, a
        recorder event and a postmortem section; a WARNING once the
        catalog is warm, none before."""
        import logging

        from paddle_tpu.telemetry import CostCatalog, FlightRecorder
        fc = FakeClock()
        tele = ServerTelemetry(clock=fc)
        cat = CostCatalog(clock=fc)
        rec = FlightRecorder(clock=fc)
        srv = _stub_server(telemetry=tele, costs=cat, recorder=rec)
        prompt = np.asarray([1, 2, 3], np.int32)

        def stall():
            fc.advance(4.0)
            srv._host_events.note("backend_compile", 3.9)
            fc.advance(1.0)
            srv._host_events.note("gc gen2", 0.31)

        fc.advance(100.0)
        srv._host_events.note("backend_compile", 9.0)   # before: not its
        fc.advance(1.0)
        assert not cat.warmed
        with caplog.at_level(logging.WARNING, logger=_SERVER_LOG):
            _stall_next_decode_wait(srv, stall)
            srv.submit(prompt, max_new_tokens=6)
            srv.run()
        assert caplog.records == []            # warm-up stalls by design
        (first,) = srv.slow_phases
        assert (first["phase"], first["tick"], first["seconds"]) == (
            "decode_wait", 1, 5.0)
        assert first["first_use"] is True
        assert first["host_events"] == [("backend_compile", 3.9),
                                        ("gc gen2", 0.31)]
        assert cat.warmed
        stats0 = dict(srv.stats)
        with caplog.at_level(logging.WARNING, logger=_SERVER_LOG):
            _stall_next_decode_wait(srv, stall)
            srv.submit(prompt, max_new_tokens=6)
            srv.submit(prompt, max_new_tokens=6)
            srv.submit(prompt, max_new_tokens=6)     # waits for a slot
            srv.run()
        assert len(srv.slow_phases) == 2
        got = srv.slow_phases[-1]
        assert got["phase"] == "decode_wait" and got["seconds"] == 5.0
        assert got["tick"] > 1 and got["start"] + 5.0 <= fc.now()
        assert (got["live"], got["queued"], got["first_use"]) == (
            2, 1, False)
        assert got["args"] == {} and got["launches_awaited"] == 0
        assert got["host_events"] == [("backend_compile", 3.9),
                                      ("gc gen2", 0.31)]
        assert srv.stats["slow_phases"] - stats0["slow_phases"] == 1
        assert srv.stats["slow_phase_s"] - stats0["slow_phase_s"] == 5.0
        assert tele.registry.get("serving_slow_phases_total").labels(
            phase="decode_wait").value == 2.0
        (line,) = [r.getMessage() for r in caplog.records]
        assert line == (f"slow phase: tick {got['tick']} decode_wait "
                        f"5.00 s (live 2, queued 1); backend_compile "
                        f"3.90 s; gc gen2 0.31 s")
        events = rec.events(kind="slow_phase")
        assert [e["phase"] for e in events] == ["decode_wait"] * 2
        assert events[-1]["host_events"] == got["host_events"]
        with srv._lock:
            bundle = srv._postmortem_locked("test")
        assert bundle["slow_phases"] == list(srv.slow_phases)
        assert bundle["stats"]["slow_phases"] == 2

    def test_a_slow_launch_carries_what_it_served(self, caplog):
        """A stall in a launch's phase has the span's arguments and
        says the shape was new; the program's own compile (the jit path
        compiles inside the first call) is among the causes, heard from
        JAX itself; a server with no catalog warns at once."""
        import logging
        fc = FakeClock()
        tele = ServerTelemetry(clock=fc)
        srv = _stub_server(telemetry=tele)
        inner = srv._ragged_fn

        def slow_launch(*a):
            fc.advance(0.5)
            return inner(*a)
        srv._ragged_fn = slow_launch
        rid = srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
        with caplog.at_level(logging.WARNING, logger=_SERVER_LOG):
            srv.run()
        (got,) = srv.slow_phases
        assert got["phase"] == "prefill_dispatch" and got["tick"] == 1
        assert got["args"] == {"width": 4, "rows": 1, "launch_rows": 8,
                               "rids": [rid]}
        assert got["first_use"] is True
        causes = dict(got["host_events"])
        assert {"jaxpr_trace", "backend_compile"} <= set(causes)
        (line,) = [r.getMessage() for r in caplog.records]
        assert line.startswith("slow phase: tick 1 prefill_dispatch 0.50 s "
                               "(live 0, queued 0); ")
        assert f"backend_compile {causes['backend_compile']:.2f} s" in line

    def test_a_wait_sits_through_every_launch_not_yet_awaited(self):
        """A long prompt's chunks launch one after another with nothing
        read back between them while no slot decodes, and the last
        launch's wait is for them all: its limit is ``SLOW_PHASE_S`` a
        launch awaited, and a record says how many there were."""
        fc = FakeClock()
        tele = ServerTelemetry(clock=fc)
        srv = _stub_server(telemetry=tele, prefill_tokens_per_tick=4)
        inner = srv._count_dispatches
        waits = []

        def counted(n=1, op="prefill"):
            # called inside prefill_wait, right after the launch's call
            if op == "prefill" and srv._unawaited == 4:
                fc.advance(waits.pop())
            return inner(n, op=op)
        srv._count_dispatches = counted
        prompt = np.arange(1, 15, dtype=np.int32)     # 4 + 4 + 4 + 2
        for seconds in (0.9, 1.1):
            waits.append(seconds)
            prompt = prompt[::-1].copy()      # no prefix to share
            rid = srv.submit(prompt, max_new_tokens=2)
            srv.run()
            assert not waits
        blocked = [e["args"]["blocked"] for e in _serve_spans(tele)
                   if e["name"] == "serve.prefill_wait"]
        assert blocked == [0, 0, 0, 1] * 2
        # 0.9 s over four launches is no stall; 1.1 s is
        (got,) = srv.slow_phases
        assert got["phase"] == "prefill_wait"
        assert got["seconds"] == pytest.approx(1.1)
        assert got["launches_awaited"] == 4
        assert got["args"] == {"blocked": 1, "width": 2, "rows": 1,
                               "launch_rows": 4, "rids": [rid]}
        assert srv.stats["slow_phases"] == 1 and srv._unawaited == 0

    def test_catalog_alone_keeps_the_record_and_its_arguments(self):
        from paddle_tpu.telemetry import CostCatalog
        fc = FakeClock()
        srv = _stub_server(costs=CostCatalog(clock=fc))
        srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
        srv.run()                                     # compiles
        inner = srv._ragged_fn
        srv._cost_program = lambda op, fn, args: fn   # no priced copy

        def slow_launch(*a):
            fc.advance(0.5)
            return inner(*a)
        srv._ragged_fn = slow_launch
        rid = srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
        srv.run()
        (got,) = srv.slow_phases
        assert got["phase"] == "prefill_dispatch"
        assert got["args"] == {"width": 4, "rows": 1, "launch_rows": 8,
                               "rids": [rid]}
        assert got["first_use"] is False
        assert srv.stats["slow_phases"] == 1
        assert srv.stats["slow_phase_s"] == pytest.approx(0.5)

    def test_idle_wait_and_a_sound_run_keep_no_record(self):
        clock = _SteppingClock()
        tele = ServerTelemetry(clock=clock)
        srv = _stub_server(telemetry=tele)
        srv.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
        srv.run()
        assert not srv.slow_phases
        assert (srv.stats["slow_phases"], srv.stats["slow_phase_s"]) == (
            0, 0.0)
        assert tele.registry.get("serving_slow_phases_total") \
            .samples() == {}
        # the serve loop's sleep is nobody's stall, however long
        from paddle_tpu.telemetry.serving import TickBoundary
        fc = FakeClock()
        wait = TickBoundary(None, ServerTelemetry(clock=fc), "idle_wait")
        fc.advance(10.0)
        wait.close()


class TestHostEventLog:
    def test_listeners_are_registered_once_and_only_with_a_boundary(self):
        """``telemetry=None, costs=None`` builds no log and registers no
        listener; the first server with a boundary registers the pair,
        a second adds none."""
        import gc

        import jax
        from paddle_tpu.telemetry import CostCatalog

        def listeners():
            return (len(jax._src.monitoring.get_event_duration_listeners()),
                    len(gc.callbacks))
        before = listeners()
        off = _stub_server()
        assert off._host_events is None and listeners() == before
        assert len(off.slow_phases) == 0
        one = _stub_server(costs=CostCatalog())
        after = listeners()
        assert one._host_events is not None
        assert all(0 <= a - b <= 1 for a, b in zip(after, before))
        two = _stub_server(telemetry=ServerTelemetry())
        assert two._host_events is not one._host_events
        assert listeners() == after

    def test_compiles_and_collector_pauses_reach_every_live_log(self):
        import gc

        import jax
        import jax.numpy as jnp
        from paddle_tpu.telemetry.serving import (GC_PAUSE_S,
                                                  HostEventLog)
        fa, fb = FakeClock(), FakeClock(50.0)
        a, b = HostEventLog(fa), HostEventLog(fb)
        fa.advance(1.0)
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 5)))   # a new program
        names = [n for _, n, _ in a.events]
        assert {"jaxpr_trace", "jaxpr_to_mlir_module",
                "backend_compile"} <= set(names)
        assert [n for _, n, _ in b.events] == names
        # each on its own clock
        assert {end for end, _, _ in a.events} == {1.0}
        assert {end for end, _, _ in b.events} == {50.0}
        assert a.ended_in(0.5, 1.0) and not a.ended_in(1.5, 2.0)
        # a saving is not time that passed; another name is kept whole
        HostEventLog._on_duration(
            "/jax/compilation_cache/compile_time_saved_sec", 2.0)
        HostEventLog._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.4)
        HostEventLog._on_duration("/jax/some/new_event", 0.1)
        assert [(n, s) for _, n, s in list(a.events)[-2:]] == [
            ("cache_retrieval", 0.4), ("new_event", 0.1)]
        # a collection shorter than GC_PAUSE_S is not kept; a long one is
        n = len(a.events)
        gc.collect()
        assert GC_PAUSE_S >= 0.01
        HostEventLog._on_gc("start", {"generation": 2})
        HostEventLog._gc_t0 -= 0.5                    # half a second ago
        HostEventLog._on_gc("stop", {"generation": 2})
        kept = list(a.events)[n:]
        assert kept[-1][1] == "gc gen2" and kept[-1][2] >= 0.5
        # a log nobody holds is fed no more
        del b
        gc.collect()
        assert len(HostEventLog._live) >= 1
        assert all(log is not None for log in HostEventLog._live)


# ---------------------------------------------------------- kernel names

def _kernel_cases():
    """(name the trace must show, a function that traces the kernel's
    wrapper at a tiny shape)."""
    import jax
    import jax.numpy as jnp
    f32, bf16, i8, i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def paged():
        from paddle_tpu.ops.pallas.paged_attention import \
            _paged_attention_pallas
        return jax.make_jaxpr(
            lambda q, k, v, bt, ln: _paged_attention_pallas(
                q, k, v, bt, ln, 0.125))(
            sds((2, 4, 64)), sds((8, 16, 4, 64)), sds((8, 16, 4, 64)),
            sds((2, 4), i32), sds((2,), i32))

    def ragged():
        from paddle_tpu.ops.pallas.ragged_prefill import \
            _ragged_prefill_pallas
        return jax.make_jaxpr(
            lambda q, k, v, bt, t0, take: _ragged_prefill_pallas(
                q, k, v, bt, t0, take, 0.125))(
            sds((2, 8, 4, 64)), sds((8, 16, 4, 64)), sds((8, 16, 4, 64)),
            sds((2, 4), i32), sds((2,), i32), sds((2,), i32))

    def flash_fwd():
        from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_pallas
        return jax.make_jaxpr(
            lambda q, k, v: _flash_fwd_pallas(q, k, v, 0.125, True))(
            sds((2, 128, 64), bf16), sds((2, 128, 64), bf16),
            sds((2, 128, 64), bf16))

    def flash_bwd():
        from paddle_tpu.ops.pallas.flash_attention import _flash_bwd_pallas
        x = sds((2, 128, 64), bf16)
        return jax.make_jaxpr(
            lambda q, k, v, o, lse, do: _flash_bwd_pallas(
                q, k, v, o, lse, do, 0.125, True))(
            x, x, x, x, sds((2, 128)), x)

    def quant():
        from paddle_tpu.ops.pallas.quant_matmul import quantized_matmul
        return jax.make_jaxpr(
            lambda x, w: quantized_matmul(x, w, 0.5, 0.5, interpret=True))(
            sds((128, 128), i8), sds((128, 128), i8))

    def gemm():
        from paddle_tpu.ops.pallas.gemm_epilogue import \
            _gemm_epilogue_pallas
        return jax.make_jaxpr(
            lambda x, w, b: _gemm_epilogue_pallas(x, w, b, "gelu"))(
            sds((128, 128)), sds((128, 128)), sds((128,)))

    def rms_fwd():
        from paddle_tpu.ops.pallas.rms_norm import _pallas_fwd
        return jax.make_jaxpr(lambda x, w: _pallas_fwd(x, w, 1e-6))(
            sds((16, 128)), sds((128,)))

    def rms_bwd():
        from paddle_tpu.ops.pallas.rms_norm import _pallas_bwd
        return jax.make_jaxpr(lambda x, w, g: _pallas_bwd(x, w, g, 1e-6))(
            sds((16, 128)), sds((128,)), sds((16, 128)))

    def rope():
        from paddle_tpu.ops.pallas.rope import apply_rotary_pallas
        return jax.make_jaxpr(apply_rotary_pallas)(
            sds((1, 16, 2, 64)), sds((16, 32)), sds((16, 32)))

    return [("paged_attention_decode", paged),
            ("ragged_prefill_attention", ragged),
            ("flash_fwd", flash_fwd),
            ("flash_bwd_dq", flash_bwd), ("flash_bwd_dkv", flash_bwd),
            ("quant_matmul", quant), ("gemm_epilogue", gemm),
            ("rms_norm_fwd", rms_fwd), ("rms_norm_bwd", rms_bwd),
            ("rope", rope)]


def _pallas_call_names(jaxpr):
    """The ``name`` of every pallas_call in a jaxpr, sub-jaxprs
    included."""
    import jax
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_call_names(sub)
    return names


class TestKernelNames:
    @pytest.mark.parametrize("name,trace", _kernel_cases(),
                             ids=[c[0] for c in _kernel_cases()])
    def test_kernel_wrapper_holds_a_pallas_call_under_its_constant_name(
            self, name, trace):
        assert name in _pallas_call_names(trace().jaxpr)

    def test_every_pallas_call_site_passes_a_name(self):
        """No kernel reaches a trace as ``%closed_call.N``: each
        ``pl.pallas_call(`` under ops/pallas is followed by a constant
        ``name=``, and no two kernels share one."""
        import os
        import re
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "paddle_tpu", "ops", "pallas")
        names = []
        for fn in sorted(os.listdir(root)):
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                src = f.read()
            calls = len(re.findall(r"pl\.pallas_call\(", src))
            named = re.findall(r'^\s+name="([a-z_]+)",$', src, re.M)
            assert calls == len(named), fn
            names += named
        assert sorted(names) == sorted(n for n, _ in _kernel_cases())


# ------------------------------------------------------------ exposition

class TestPrometheusExposition:
    def test_round_trip_through_parser(self):
        reg = MetricRegistry()
        c = reg.counter("req_total", "requests", labelnames=("state",))
        c.labels(state="ok").inc(3)
        c.labels(state='we"ird\\l').inc()       # label escaping
        reg.gauge("depth", "queue depth").set(2.5)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.7)
        text = render_prometheus(reg)
        parsed = parse_prometheus(text)
        assert parsed[("req_total", (("state", "ok"),))] == 3.0
        assert parsed[("req_total", (("state", 'we"ird\\l'),))] == 1.0
        assert parsed[("depth", ())] == 2.5
        assert parsed[("lat_seconds_bucket", (("le", "0.1"),))] == 1.0
        assert parsed[("lat_seconds_bucket", (("le", "+Inf"),))] == 2.0
        assert parsed[("lat_seconds_sum", ())] == pytest.approx(0.75)
        assert parsed[("lat_seconds_count", ())] == 2.0
        # every rendered sample line survives the round trip
        n_samples = sum(1 for line in text.splitlines()
                        if line and not line.startswith("#"))
        assert len(parsed) == n_samples

    def test_http_metrics_and_stats(self):
        import urllib.request
        reg = MetricRegistry()
        reg.counter("hits_total").inc(7)
        with MetricsServer(reg, port=0,
                           extra_stats=lambda: {"extra": 1}) as ms:
            txt = urllib.request.urlopen(
                ms.url + "/metrics", timeout=10).read().decode()
            stats = json.loads(urllib.request.urlopen(
                ms.url + "/stats", timeout=10).read())
            with pytest.raises(Exception):
                urllib.request.urlopen(ms.url + "/nope", timeout=10)
        assert parse_prometheus(txt)[("hits_total", ())] == 7.0
        assert stats["stats"] == {"extra": 1}
        assert stats["metrics"]["hits_total"]["samples"][0]["value"] == 7.0


# ----------------------------------------------------- serving SLO stack

class TestServerSLO:
    def test_scripted_run_exact_histograms(self):
        """Dense server, fake clock: submit a@t=0 and b@t=1, admit both
        at t=2, tick every 0.5s -> every latency histogram is exact."""
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        tele, fc, reg = _scripted_telemetry()
        srv = ContinuousBatchingServer(_model(), max_slots=2,
                                       max_cache_len=64, telemetry=tele)
        rng = np.random.default_rng(0)
        ra = srv.submit(rng.integers(0, 256, (4,)).astype(np.int32),
                        max_new_tokens=4)
        fc.advance(1.0)
        rb = srv.submit(rng.integers(0, 256, (5,)).astype(np.int32),
                        max_new_tokens=3)
        fc.advance(1.0)
        while srv.step():
            fc.advance(0.5)
        outs = srv.run()
        assert set(outs) == {ra, rb}

        req = reg.get("serving_requests_total")
        assert req.labels(state="submitted").value == 2.0
        assert req.labels(state="finished").value == 2.0
        assert req.labels(state="failed").value == 0.0
        # a waits 2s, b waits 1s; first token lands at admission
        assert _hist(reg, "serving_queue_wait_seconds") == (2, 3.0)
        assert _hist(reg, "serving_ttft_seconds") == (2, 3.0)
        # b finishes at t=2.5 (3 tokens), a at t=3.0 (4 tokens)
        assert _hist(reg, "serving_e2e_seconds") == \
            (2, pytest.approx(1.5 + 3.0))
        assert _hist(reg, "serving_tpot_seconds") == \
            (2, pytest.approx(0.5 / 2 + 1.0 / 3))
        # 3 ticks: occupancy 2, 2, 1; decode tokens 2 + 2 + 1
        assert _hist(reg, "serving_tick_occupancy") == (3, 5.0)
        n_ticks, tick_sum = _hist(reg, "serving_tick_seconds")
        assert n_ticks == 3 and tick_sum == 0.0     # fake clock: 0-dur
        tok = reg.get("serving_tokens_total")
        assert tok.labels(kind="prefill").value == 9.0
        assert tok.labels(kind="decode").value == 5.0
        assert tok.labels(kind="prefix_hit").value == 0.0
        pfx = reg.get("serving_prefix_cache_total")
        assert pfx.labels(result="hit").value == 0.0
        assert pfx.labels(result="miss").value == 2.0
        assert reg.get("serving_queue_depth").value == 0.0
        assert reg.get("serving_active_slots").value == 0.0

    def test_request_lifecycle_spans(self):
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        tele, fc, reg = _scripted_telemetry()
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32, telemetry=tele)
        rid = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        fc.advance(2.0)
        while srv.step():
            fc.advance(0.5)
        srv.run()
        evs = tele.tracer.events()
        spans = {e["name"]: e for e in evs}
        assert spans["request.queued"]["args"]["rid"] == rid
        assert spans["request.queued"]["dur"] == pytest.approx(2e6)
        # prefill span sits between queued and decode (0-dur: the fake
        # clock does not advance inside one step() call)
        assert spans["request.prefill"]["ts"] == pytest.approx(2e6)
        assert spans["request.prefill"]["args"]["prefill_tokens"] == 4
        # first token at t=2; tick at t=2 emits token 2, the t=2.5 tick
        # emits token 3 and the same step harvests -> decode span 0.5s
        assert spans["request.decode"]["dur"] == pytest.approx(5e5)
        assert spans["request.decode"]["args"]["tokens"] == 3

    def test_cancel_and_queue_depth(self):
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        tele, fc, reg = _scripted_telemetry()
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32, telemetry=tele)
        ra = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=8)
        rb = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=8)
        assert reg.get("serving_queue_depth").value == 2.0
        assert srv.cancel(rb)
        assert reg.get("serving_queue_depth").value == 1.0
        srv.step()
        assert srv.cancel(ra)                      # mid-decode
        req = reg.get("serving_requests_total")
        assert req.labels(state="canceled").value == 2.0
        assert req.labels(state="finished").value == 0.0

    def test_active_slots_gauge_clears_on_pre_decode_harvest(self):
        """code-review r6: a slot admitted by the previous tick's tail
        that finishes without decoding (budget 1) is harvested BEFORE
        the decode dispatch — the early return must still zero the
        active-slots gauge, not leave a phantom busy slot."""
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        tele, fc, reg = _scripted_telemetry()
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32, telemetry=tele)
        ra = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
        rb = srv.submit(np.arange(5, dtype=np.int32), max_new_tokens=1)
        while srv.step():
            fc.advance(0.5)
        srv.step()                       # idle tick must also report 0
        assert reg.get("serving_active_slots").value == 0.0
        outs = srv.run()
        assert len(outs[ra]) == 4 and len(outs[rb]) == 1

    def test_paged_pool_gauges_prefix_hits_null_writes(self):
        """Paged backend: page-pool occupancy gauges and the
        null-redirected-write counter match hand-computed values."""
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        tele, fc, reg = _scripted_telemetry()
        srv = ContinuousBatchingServer(_model(), max_slots=2,
                                       max_cache_len=64,
                                       cache_backend="paged", page_size=8,
                                       telemetry=tele)
        usable = srv._kv.num_pages - 1              # 2*8 = 16
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, 256, (8,)).astype(np.int32)
        srv.register_prefix(prefix)                 # pins 1 full page
        pool = reg.get("kv_pool_pages")
        assert pool.labels(state="pinned").value == 1.0
        assert pool.labels(state="free").value == usable - 1
        assert pool.labels(state="live").value == 0.0

        prompt = np.concatenate(
            [prefix, rng.integers(0, 256, (4,)).astype(np.int32)])
        rid = srv.submit(prompt, max_new_tokens=4)  # extent 16 -> 2 pages
        srv.step()                                  # admit: 1 own page
        assert pool.labels(state="live").value == 1.0
        assert pool.labels(state="free").value == usable - 2
        pfx = reg.get("serving_prefix_cache_total")
        assert pfx.labels(result="hit").value == 1.0
        tok = reg.get("serving_tokens_total")
        assert tok.labels(kind="prefix_hit").value == 8.0
        assert tok.labels(kind="prefill").value == 8.0 + 4.0  # reg + rest

        out = srv.run()[rid]
        assert len(out) == 4
        # finished: own page freed, shared page back to pinned-only
        assert pool.labels(state="live").value == 0.0
        assert pool.labels(state="free").value == usable - 1
        assert pool.labels(state="pinned").value == 1.0
        # each tick stepped 1 inactive slot whose writes null-redirect
        n_ticks, _ = _hist(reg, "serving_tick_occupancy")
        assert reg.get("kv_null_redirected_writes_total").value == n_ticks
        # allocator churn counters (kv_cache telemetry_stats)
        ks = srv._kv.telemetry_stats()
        assert ks["alloc_total"] == 2 and ks["freed_total"] == 1
        assert ks["shared_ref_total"] == 1

    def test_admission_failure_counted(self):
        tele, fc, reg = _scripted_telemetry()
        tele.on_submit(7, 8, 1)
        tele.on_admit(7, 0)
        tele.on_admission_failure(7, ValueError("boom"))
        req = reg.get("serving_requests_total")
        assert req.labels(state="failed").value == 1.0
        (ev,) = [e for e in tele.tracer.events()
                 if e["name"] == "request.failed"]
        assert ev["args"] == {"rid": 7, "error": "ValueError"}

    def test_serve_metrics_http_hook(self):
        import urllib.request
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        from paddle_tpu.inference.serving import serve_metrics
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32,
                                       cache_backend="paged", page_size=8,
                                       telemetry=True)
        rid = srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        srv.run()
        ms = serve_metrics(srv)
        try:
            txt = urllib.request.urlopen(
                ms.url + "/metrics", timeout=10).read().decode()
            stats = json.loads(urllib.request.urlopen(
                ms.url + "/stats", timeout=10).read())
        finally:
            ms.close()
        parsed = parse_prometheus(txt)
        assert parsed[("serving_requests_total",
                       (("state", "finished"),))] == 1.0
        assert stats["stats"]["prefill_tokens"] == 4
        assert stats["stats"]["kv_pool"]["num_pages"] == srv._kv.num_pages

    def test_serve_metrics_requires_telemetry(self):
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        from paddle_tpu.inference.serving import serve_metrics
        srv = ContinuousBatchingServer(_model(), max_slots=1,
                                       max_cache_len=32)
        with pytest.raises(ValueError, match="telemetry"):
            serve_metrics(srv)


# --------------------------------------------------- scheduler + training

class TestSchedulerMetrics:
    def test_batch_scheduler_publishes(self):
        from paddle_tpu.inference.serving import BatchScheduler
        reg = MetricRegistry()
        sched = BatchScheduler(lambda xs: [xs[0] * 2.0], max_batch_size=8,
                               max_delay_ms=5, registry=reg)
        futs = [sched.submit(np.ones((2, 3), np.float32))
                for _ in range(3)]
        for f in futs:
            f.result(timeout=20)
        sched.close()
        assert reg.get("scheduler_requests_total").value == 3.0
        assert reg.get("scheduler_batches_total").value >= 1.0
        h = reg.get("scheduler_batch_rows")
        assert h.sum == 6.0                     # 3 requests x 2 rows
        assert reg.get("scheduler_queue_wait_seconds").count == 3

    def test_failure_counter(self):
        from paddle_tpu.inference.serving import BatchScheduler
        reg = MetricRegistry()
        sched = BatchScheduler(lambda xs: 1 / 0, max_delay_ms=1,
                               registry=reg)
        f = sched.submit(np.ones((1, 2), np.float32))
        with pytest.raises(ZeroDivisionError):
            f.result(timeout=20)
        sched.close()
        assert reg.get("scheduler_failures_total").value == 1.0

    def test_rejected_submit_not_counted(self):
        """code-review r6: a submit() on a closed scheduler raises and
        must NOT bump scheduler_requests_total."""
        from paddle_tpu.inference.serving import BatchScheduler
        reg = MetricRegistry()
        sched = BatchScheduler(lambda xs: [xs[0]], registry=reg)
        sched.submit(np.ones((1, 2), np.float32)).result(timeout=20)
        sched.close()
        with pytest.raises(RuntimeError, match="closed"):
            sched.submit(np.ones((1, 2), np.float32))
        assert reg.get("scheduler_requests_total").value == 1.0


class TestTrainingBridge:
    def test_hapi_callback_metrics(self):
        from paddle_tpu.hapi.callbacks import TelemetryCallback
        fc = FakeClock()
        reg = MetricRegistry()
        cb = TelemetryCallback(reg, clock=fc, tokens_per_batch=256,
                               tracer=Tracer(clock=fc))
        cb.on_epoch_begin(0)
        for step in range(3):
            cb.on_train_batch_begin(step)
            fc.advance(0.5)
            cb.on_train_batch_end(step, {"loss": 1.0 / (step + 1)})
        cb.on_epoch_end(0)
        assert reg.get("train_steps_total").value == 3.0
        assert reg.get("train_tokens_total").value == 768.0
        assert _hist(reg, "train_step_seconds") == (3, pytest.approx(1.5))
        assert reg.get("train_loss").value == pytest.approx(1.0 / 3)
        assert reg.get("train_throughput").value == pytest.approx(512.0)
        (ep,) = [e for e in cb.tracer.events()
                 if e["name"] == "train.epoch"]
        assert ep["dur"] == pytest.approx(1.5e6)

    def test_hapi_fit_integration(self):
        """TelemetryCallback rides Model.fit end to end."""
        from paddle_tpu.hapi.callbacks import TelemetryCallback
        from paddle_tpu.io import TensorDataset
        reg = MetricRegistry()
        x = np.random.RandomState(0).randn(32, 4).astype(np.float32)
        y = (x.sum(-1, keepdims=True) > 0).astype(np.float32)
        net = pt.nn.Sequential(pt.nn.Linear(4, 8), pt.nn.ReLU(),
                               pt.nn.Linear(8, 1))
        model = pt.Model(net)
        model.prepare(optimizer=pt.optimizer.SGD(
            learning_rate=0.1, parameters=net.parameters()),
            loss=pt.nn.BCEWithLogitsLoss())
        model.fit(TensorDataset([x, y]), batch_size=16, epochs=1,
                  verbose=0, shuffle=False,
                  callbacks=[TelemetryCallback(reg, samples_per_batch=16)])
        assert reg.get("train_steps_total").value == 2.0
        assert reg.get("train_samples_total").value == 32.0
        assert reg.get("train_loss").value > 0
        assert reg.get("train_step_seconds").count == 2

    def test_step_timer_bridge(self):
        from paddle_tpu.profiler import StepTimer, profiler_step_timer
        reg = MetricRegistry()
        t = StepTimer().publish_to(reg, prefix="fit_step")
        t.start()
        t.step()
        t.step()
        t.stop()
        h = reg.get("fit_step_seconds")
        # total_time also includes the step2 -> stop() tail segment
        assert h.count == 2 and 0 < h.sum <= t.total_time
        assert reg.get("fit_step_ips").value > 0
        with profiler_step_timer(registry=reg, prefix="loop") as lt:
            lt.step()
            lt.step()
        # start() arms t0, so both steps observe a segment
        assert reg.get("loop_seconds").count == 2

    def test_metric_publish_bridge(self):
        from paddle_tpu.metric import Accuracy, publish
        reg = MetricRegistry()
        acc = Accuracy(topk=(1, 2))
        acc.update(acc.compute(
            np.array([[0.9, 0.05, 0.05], [0.2, 0.7, 0.1]], np.float32),
            np.array([0, 2])))
        publish(acc, reg, name="eval_acc")
        g = reg.get("eval_acc")
        assert g.labels(component="acc_top1").value == 0.5
        assert g.labels(component="acc_top2").value == 0.5


# -------------------------------------------------------------- overhead

class TestDisabledOverheadStructural:
    def test_disabled_instruments_are_allocation_free_singletons(self):
        """The deterministic half of the <2% overhead target (the
        timing half is benchmarks/telemetry_overhead_bench.py): every
        disabled-path operation resolves to the SAME no-op object, and
        a scripted server run performs zero clock reads."""
        reg = MetricRegistry(enabled=False)
        insts = {reg.counter("a"), reg.gauge("b"), reg.histogram("c"),
                 reg.counter("a").labels(x=1)}
        assert insts == {NULL_INSTRUMENT}
        fc = FakeClock()
        tele = ServerTelemetry(registry=reg, clock=fc)
        for _ in range(100):
            tele.on_tick(0.01, 4, 4)
        assert fc.reads == 0


@pytest.mark.slow
@pytest.mark.bench
class TestEnabledOverheadTiming:
    def test_enabled_decode_tick_overhead_bounded(self):
        """Wall-clock guard for the telemetry bench (target <2% there;
        this CI-variance-tolerant bound only catches order-of-magnitude
        regressions like a lock or sync landing on the tick path)."""
        import time
        from paddle_tpu.inference.continuous_batching import \
            ContinuousBatchingServer
        model = _model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (6,)).astype(np.int32)
                   for _ in range(4)]

        def drain(telemetry):
            srv = ContinuousBatchingServer(model, max_slots=4,
                                           max_cache_len=64,
                                           telemetry=telemetry)
            for p in prompts:                    # warm the compiles
                srv.submit(p, max_new_tokens=4)
            srv.run()
            best = float("inf")
            for _ in range(3):
                for p in prompts:
                    srv.submit(p, max_new_tokens=32)
                t0 = time.perf_counter()
                srv.run()
                best = min(best, time.perf_counter() - t0)
            return best

        off = drain(None)
        on = drain(ServerTelemetry())
        assert on < off * 1.5, (on, off)
