"""A stream's first token is handed over before the decode of its turn.

A turn whose admission draws the first token of a request with an
``on_token`` callback ends after the launch: the callback fires outside
the lock, and the next turn runs the decode the slots are owed and
admits nothing first (``_step_inner``, ``_turn``). Held here: the
callback runs before the decode is dispatched; the tokens equal those
of the same requests served without a stream, whatever the backend,
sampling, block, preemption or migration; the programs still alternate
launch, decode; traffic without a stream never splits a turn; a
callback that raises fails only its request and the decode stays owed;
and the counter counts the turns that split.
"""
import numpy as np
import pytest

from _serving_stub import StubModel, stub_tokens
from paddle_tpu.inference.continuous_batching import ContinuousBatchingServer
from paddle_tpu.reliability import CallbackError, FaultInjector, faults
from paddle_tpu.telemetry import ServerTelemetry
from test_slot_state_fold import SAMPLED, SEEDS, _llama, _prompts


class _Streams:
    """Every chunk a request streamed, and the server's decode count
    when each arrived."""

    def __init__(self, srv):
        self.srv = srv
        self.chunks = {}
        self.ticks = {}

    def __call__(self, rid, toks):
        self.chunks.setdefault(rid, []).extend(int(t) for t in toks)
        self.ticks.setdefault(rid, []).append(self.srv.stats["decode_ticks"])


def _log_programs(srv):
    """Wrap the server's dispatch sites: ``log`` lists "prefill",
    "decode" and "handover" (callbacks fired while a decode is owed) in
    the order they ran."""
    log = []
    count, tick, fire = (srv._count_dispatches, srv._tick_dispatch,
                         srv._fire_callbacks)

    def counted(n=1, op="prefill"):
        if op == "prefill":
            log.append("prefill")
        return count(n, op=op)

    def ticked(op, n=1):
        if op == "decode":
            log.append("decode")
        return tick(op, n)

    def fired():
        if srv._owed_decode:
            log.append("handover")
        return fire()

    srv._count_dispatches, srv._tick_dispatch = counted, ticked
    srv._fire_callbacks = fired
    return log


def _assert_alternates(log):
    """After every handover the next program is the owed decode."""
    for i, e in enumerate(log):
        if e == "handover":
            nxt = [p for p in log[i + 1:] if p != "handover"]
            assert nxt and nxt[0] == "decode", log


def _served(make, prompts, budget, stream, seeds=SEEDS):
    srv = make()
    streams = _Streams(srv)
    log = _log_programs(srv)
    rids = [srv.submit(p, max_new_tokens=budget, seed=seeds[i % len(seeds)],
                       on_token=streams if stream else None)
            for i, p in enumerate(prompts)]
    outs = srv.run()
    return srv, [np.asarray(outs[r]) for r in rids], streams, rids, log


def _llama_make(**kw):
    return lambda: ContinuousBatchingServer(_llama(), max_cache_len=64, **kw)


def _preempt_make():
    from paddle_tpu.reliability import CircuitBreaker, RetryPolicy
    return ContinuousBatchingServer(
        StubModel(), max_slots=4, max_cache_len=64, cache_backend="paged",
        page_size=8, num_pages=9, admission="optimistic", temperature=8.0,
        do_sample=True, retry_policy=RetryPolicy(base_delay_s=0.0,
                                                 jitter=0.0),
        breaker=CircuitBreaker(failure_threshold=10_000))


# (make, prompts, budget): more requests than slots where it matters,
# so admissions also land in later turns
CASES = {
    "greedy": (_llama_make(max_slots=2, cache_backend="paged",
                           page_size=8), _prompts(4), 7),
    "sampled": (_llama_make(max_slots=2, cache_backend="paged", page_size=8,
                            **SAMPLED), _prompts(4), 7),
    "tick_block_2": (_llama_make(max_slots=2, cache_backend="paged",
                                 page_size=8, tick_block=2, **SAMPLED),
                     _prompts(4), 7),
    "dense_backend": (_llama_make(max_slots=2, cache_backend="dense",
                                  **SAMPLED), _prompts(4), 7),
    "preempt_resume": (_preempt_make, _prompts(8, 4, 12, vocab=16, seed=3),
                       28),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tokens_equal_the_unstreamed_run(case):
    """Same programs on the same inputs in the same order: the tokens
    equal those of the run without streams, each stream carries exactly
    its request's tokens (a resumed slot re-sends nothing), and every
    split hands a first token over before the decode it owes."""
    make, prompts, budget = CASES[case]
    quiet_srv, quiet, _, _, quiet_log = _served(make, prompts, budget, False)
    srv, outs, streams, rids, log = _served(make, prompts, budget, True)
    for a, b in zip(outs, quiet):
        np.testing.assert_array_equal(a, b)
    for rid, out in zip(rids, outs):
        assert streams.chunks[rid] == out.tolist()
    assert quiet_srv.stats["handover_turns"] == 0
    assert 0 < srv.stats["handover_turns"] <= len(prompts)
    assert log.count("handover") == srv.stats["handover_turns"]
    _assert_alternates(log)
    # the device sees the same programs in the same order
    assert [e for e in log if e != "handover"] == quiet_log
    if case == "preempt_resume":
        assert srv.stats["preemptions"] > 0
        assert srv.stats["preemptions"] == quiet_srv.stats["preemptions"]


def test_migrated_in_request_streams_the_same_tokens():
    """A sampled request paused mid-decode and restored on a sibling:
    the same tokens with streams as without, the target's stream
    carries the rest of the chain, and a restored slot, past its first
    token, never splits the target's turn."""
    def run(stream):
        kw = dict(max_slots=2, max_cache_len=64, cache_backend="paged",
                  page_size=8, num_pages=17, do_sample=True,
                  temperature=8.0)
        src, tgt = (ContinuousBatchingServer(StubModel(), **kw)
                    for _ in range(2))
        sa, sb = _Streams(src), _Streams(tgt)
        prompt = np.arange(1, 12, dtype=np.int32) % 13
        rid = src.submit(prompt, max_new_tokens=20, seed=2**32 + 9,
                         on_token=sa if stream else None)
        other = src.submit(prompt[::-1].copy(), max_new_tokens=20, seed=4,
                           on_token=sa if stream else None)
        for _ in range(5):
            src.step()
        state, payloads = src.migrate_out(rid)
        moved = tgt.migrate_in(state, payloads,
                               on_token=sb if stream else None)
        src.migrate_finish(rid)
        outs = [tgt.run()[moved], src.run()[other]]
        if stream:
            assert src.stats["handover_turns"] == 1
            assert tgt.stats["handover_turns"] == 0
            assert sa.chunks[rid] + sb.chunks[moved] == outs[0].tolist()
            assert sa.chunks[other] == outs[1].tolist()
        return outs

    for a, b in zip(run(True), run(False)):
        np.testing.assert_array_equal(a, b)


def test_first_callback_runs_before_the_decode_of_its_turn():
    """Inside the first callback the decode count is what it was before
    the turn; ``step()`` still makes one decode dispatch; token 2 comes
    from a decode that ran after token 1 was handed over."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4)
    streams = _Streams(srv)
    p = np.asarray([1, 2, 3], np.int32)
    rid = srv.submit(p, max_new_tokens=5, on_token=streams)
    before = srv.stats["decode_ticks"]
    srv.step()
    assert streams.ticks[rid] == [before, before + 1]
    assert srv.stats["decode_ticks"] == before + 1
    assert srv.stats["handover_turns"] == 1
    np.testing.assert_array_equal(srv.run()[rid], stub_tokens(p, 5))
    assert streams.chunks[rid] == stub_tokens(p, 5).tolist()


def test_each_step_makes_one_decode_dispatch():
    """Requests joining between steps split the turn that admits them;
    every ``step()`` still dispatches exactly one decode, and no second
    launch runs between a handover and its decode."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4)
    log = _log_programs(srv)
    streams = _Streams(srv)
    prompts = [np.asarray(p, np.int32) for p in
               ([1, 2, 3], [4, 5], [6, 7, 8, 9], [3, 1])]
    rids = [srv.submit(prompts[0], max_new_tokens=6, on_token=streams)]
    deltas = []
    for i in range(10):
        if i in (2, 3, 5):
            p = prompts[len(rids)]
            rids.append(srv.submit(p, max_new_tokens=6, on_token=streams))
        before = srv.stats["decode_ticks"]
        srv.step()
        deltas.append(srv.stats["decode_ticks"] - before)
    assert deltas == [1] * 10
    assert srv.stats["handover_turns"] == 4
    _assert_alternates(log)
    outs = srv.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], stub_tokens(p, 6))


def test_dense_handover_runs_no_inline_admission_before_its_decode():
    """On the dense backend an admission prefills inline: the turn that
    owes a decode admits nothing before it, even with requests queued
    for a free slot; its end-of-turn admission runs after the decode,
    as every turn's does."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=3,
                                   max_cache_len=32, cache_backend="dense")
    log = _log_programs(srv)
    streams = _Streams(srv)
    a, b = np.asarray([1, 2, 3], np.int32), np.asarray([4, 5], np.int32)
    ra = srv.submit(a, max_new_tokens=5, on_token=streams)
    rb = []
    srv._turn = _between_halves(srv, lambda: rb.append(
        srv.submit(b, max_new_tokens=5, on_token=streams)))
    srv.step()
    # turn 1: a's prefill, its handover, then the decode; b, queued
    # between the halves, is admitted after that decode
    assert log[:3] == ["prefill", "handover", "decode"]
    assert log[3] == "prefill"
    assert srv.stats["handover_turns"] == 1
    outs = srv.run()
    np.testing.assert_array_equal(outs[ra], stub_tokens(a, 5))
    np.testing.assert_array_equal(outs[rb[0]], stub_tokens(b, 5))
    _assert_alternates(log)


def _between_halves(srv, action):
    """``srv._turn`` that runs ``action`` once, with the lock free,
    between a handover and the decode it owes."""
    turn, fire, done = srv._turn, srv._fire_callbacks, []

    def fired():
        owed = srv._owed_decode
        fire()
        if owed and not done:
            done.append(True)
            action()

    def wrapped(idle=False):
        srv._fire_callbacks = fired
        try:
            return turn(idle=idle)
        finally:
            srv._fire_callbacks = fire
    return wrapped


def test_traffic_without_streams_never_splits():
    """Requests without ``on_token`` keep the turn whole: no handover,
    and the launch and its decode share a turn as before."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=2,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4)
    prompts = [np.asarray(p, np.int32) for p in ([1, 2, 3], [4, 5], [6])]
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    before = srv.stats["decode_ticks"]
    srv.step()
    assert srv.stats["decode_ticks"] == before + 1
    outs = srv.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], stub_tokens(p, 4))
    assert srv.stats["handover_turns"] == 0


def test_handover_counter_counts_split_turns():
    """One count a turn that split, however many first tokens it handed
    over; a request whose budget ends with its first token finishes in
    the turn that drew it, and with nothing else decoding that turn owes
    no decode; the registry mirrors the count."""
    tele = ServerTelemetry()
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4, telemetry=tele)
    streams = _Streams(srv)
    a, b, c = (np.asarray(p, np.int32) for p in ([1, 2], [3, 4, 5], [6]))
    srv.submit(a, max_new_tokens=4, on_token=streams)
    srv.submit(b, max_new_tokens=4, on_token=streams)
    srv.step()                         # one launch, two first tokens
    assert srv.stats["handover_turns"] == 1
    srv.run()
    assert srv.stats["handover_turns"] == 1
    ticks = srv.stats["decode_ticks"]
    rc = srv.submit(c, max_new_tokens=1, on_token=streams)
    assert srv.step() == 0             # c ends with its first token
    assert not srv._owed_decode
    assert srv.stats["decode_ticks"] == ticks
    assert srv.stats["handover_turns"] == 1
    assert streams.chunks[rc] == stub_tokens(c, 1).tolist()
    srv.submit(c, max_new_tokens=3, on_token=streams)
    srv.run()
    assert srv.stats["handover_turns"] == 2
    got = tele.registry.get("serving_handover_turns_total").value
    assert got == srv.stats["handover_turns"]


def test_callback_fault_in_the_handover_leaves_the_decode_owed():
    """``step()``: a first callback that raises surfaces as
    ``CallbackError`` before the decode; the decode stays owed, and the
    next ``step()`` runs it and admits nothing first."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4)
    streams = _Streams(srv)

    def poisoned(rid, toks):
        raise RuntimeError("client went away")

    a, b = np.asarray([1, 2, 3], np.int32), np.asarray([4, 5], np.int32)
    bad = srv.submit(a, max_new_tokens=4, on_token=poisoned)
    good = srv.submit(b, max_new_tokens=4, on_token=streams)
    with pytest.raises(CallbackError) as ei:
        srv.step()
    assert [rid for rid, _ in ei.value.errors] == [bad]
    assert srv.stats["decode_ticks"] == 0 and srv._owed_decode
    assert streams.chunks[good] == stub_tokens(b, 1).tolist()
    srv.cancel(bad)
    late = srv.submit(a, max_new_tokens=2, on_token=streams)
    launches = srv.stats["prefill_dispatches"]
    srv.step()
    assert srv.stats["decode_ticks"] == 1
    assert srv.stats["prefill_dispatches"] == launches
    assert srv.queue_depth() == 0          # admitted at the turn's end
    outs = srv.run()
    np.testing.assert_array_equal(outs[good], stub_tokens(b, 4))
    np.testing.assert_array_equal(outs[late], stub_tokens(a, 2))


def test_serve_thread_fails_only_the_poisoned_stream():
    """The serve thread: a callback raising in the handover fails its
    own request; the request beside it gets its decode and every token."""
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4)
    streams = _Streams(srv)

    def poisoned(rid, toks):
        raise RuntimeError("client went away")

    a, b = np.asarray([1, 2, 3], np.int32), np.asarray([4, 5], np.int32)
    # queued before the thread starts: both activate in its first turn
    bad = srv.submit(a, max_new_tokens=4, on_token=poisoned)
    good = srv.submit(b, max_new_tokens=6, on_token=streams)
    srv.start()
    with pytest.raises(CallbackError):
        srv.wait(bad, timeout=60)
    np.testing.assert_array_equal(srv.wait(good, timeout=60),
                                  stub_tokens(b, 6))
    assert streams.chunks[good] == stub_tokens(b, 6).tolist()
    assert streams.ticks[good][1] > streams.ticks[good][0]
    assert srv.stats["handover_turns"] >= 1
    srv.stop()


def test_decode_fault_after_a_handover_is_retried_as_the_decode():
    """A decode that fails after a handover stays owed: the retried turn
    decodes before any launch."""
    fi = FaultInjector().on(faults.DECODE_TICK, schedule=[0])
    srv = ContinuousBatchingServer(StubModel(), max_slots=4,
                                   max_cache_len=32, cache_backend="paged",
                                   page_size=4, fault_injector=fi)
    log = _log_programs(srv)
    streams = _Streams(srv)
    a, b = np.asarray([1, 2, 3], np.int32), np.asarray([4, 5], np.int32)
    ra = srv.submit(a, max_new_tokens=4, on_token=streams)
    with pytest.raises(Exception):
        srv.step()                     # handover, then the decode fails
    assert srv._owed_decode and srv.stats["decode_ticks"] == 0
    rb = srv.submit(b, max_new_tokens=4, on_token=streams)
    srv.step()
    assert log[:3] == ["prefill", "handover", "decode"]
    outs = srv.run()
    np.testing.assert_array_equal(outs[ra], stub_tokens(a, 4))
    np.testing.assert_array_equal(outs[rb], stub_tokens(b, 4))
    _assert_alternates(log)
