"""The five readers of what the tick loop says about the time AROUND the
chip's programs (PR 36): a decode tick divided into dispatch and wait, a first
token's delivery, dispatches a tick, and the seconds of a window inside phases
that stalled. Each reader on hand-made observations gives the number, and
``None`` where its source is missing; ``tick_host_share`` and
``admit_ms_per_req`` read a registry of the divided tick exactly as they read
the undivided one; the rehearsed chat cell prints the four that need no chip.

The readers have NO entry in ``BENCHMARK.json`` yet (``PENDING`` below): an
entry for the serving cells has them measured anew, so the entries wait for a
``benchmark`` PR that measures them so (as the three of
``test_perfbench_nemotron_h.py`` do). The last test here runs them in a copy of
the benchmark."""
import json
import os
import shutil

import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics import (admit_ms_per_req, decode_dispatch_ms,
                                     decode_wait_chip_share,
                                     dispatches_per_tick,
                                     first_token_delivery_ms, stalled_s,
                                     tick_host_share)
from test_perfbench_rehearse import ROOT, rehearse
from test_perfbench_tick_metrics import hist, registry, serving_obs

CHAT = "gpt2-medium.chat-steady"
LONG = "keye-vl2-30b-a3b.longctx-steady"
REASON = "nemotron3-super-120b-a12b.reason-steady"
# the cells that judge the gap between tokens; the long-context cell judges
# ``ttft_p50_ms`` alone, and the reasoning cell no ``ttft_p75_ms``
GAP_CELLS = [CHAT, "lfm2-24b-a2b.assist-steady", REASON]
P75_CELLS = [c for c in GAP_CELLS if c != REASON]


def _entry(name, unit, better, source, moves, cells):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": "scheduler", "moves": moves, "workloads": cells}


# what a ``benchmark`` PR appends to ``per_layer`` once the reasoning cell is
# steady enough to be measured anew
PENDING = [
    _entry("decode_dispatch_ms", "ms", "lower", "program_span",
           "itl_p95_ms", GAP_CELLS),
    _entry("decode_wait_chip_share", "%", "higher", "device_trace",
           "itl_p95_ms", GAP_CELLS),
    _entry("first_token_delivery_ms", "ms", "lower", "program_span",
           "ttft_p50_ms", GAP_CELLS + [LONG]),
    _entry("dispatches_per_tick", "1/tick", "lower", "program_counter",
           "itl_p95_ms", GAP_CELLS),
    _entry("stalled_s", "s", "lower", "program_counter", "ttft_p75_ms",
           P75_CELLS),
]
# the long-context cell reports neither ``itl_p95_ms`` nor ``ttft_p75_ms``, and
# a metric moves an end-to-end metric of every cell it lists: there the same
# four readers go under names of its own (as PR 25's do), if they are wanted
PENDING_LONGCTX = [
    _entry(m["name"] + ".longctx", m["unit"], m["better"], m["source"],
           "ttft_p50_ms", [LONG])
    for m in PENDING if LONG not in m["workloads"]]
# the reasoning cell's 75th percentile is a per-layer metric of its own
# (``ttft_p75_ms.reason``), so there ``stalled_s`` moves the median
PENDING_REASON = [
    _entry(m["name"] + ".reason", m["unit"], m["better"], m["source"],
           "ttft_p50_ms", [REASON])
    for m in PENDING if REASON not in m["workloads"]]


# ------------------------------------------------------------ the readers
def phases(split):
    """(start, end) registries of a window of 100 ticks and 10 launches.
    ``split``: the tick divides a program's time into dispatch and wait."""
    warm = {"admit": (1.0, 50), "decode_wait": (5.0, 50),
            "idle_wait": (2.0, 9)}
    end = {"expire": (0.01, 100), "admit": (1.0 + 0.2, 350),
           "prefill_pack": (0.03, 10), "activate": (0.06, 10),
           "state_push": (0.1, 100), "emit": (0.2, 100),
           "harvest": (0.1, 200), "callbacks": (0.3, 100),
           "idle_wait": (2.0 + 3.0, 500)}
    if split:
        warm["decode_dispatch"] = (0.5, 50)
        end.update(decode_dispatch=(0.5 + 0.04, 150),
                   decode_wait=(5.0 + 0.31, 150),
                   prefill_dispatch=(0.02, 10), prefill_wait=(0.48, 10))
    else:
        end.update(decode_wait=(5.0 + 0.35, 150), prefill_wait=(0.5, 10))
    return registry(warm), registry(end)


def trace(median_s):
    return {"modules": {"jit_decode_tick": {"runs": 9, "total_s": 9 * median_s,
                                            "median_s": median_s}}}


def stats_obs(start, end, **more):
    return dict({"server_stats": {"start": start, "end": end}}, **more)


def test_decode_dispatch_ms_is_the_windows_mean():
    obs = serving_obs(*phases(split=True))
    # 0.04 s over the window's 100 ticks; warm-up's 50 are subtracted
    assert decode_dispatch_ms.read(obs) == pytest.approx(0.4)


def test_decode_wait_chip_share_is_device_time_over_the_time_given():
    obs = dict(serving_obs(*phases(split=True)), trace=trace(0.00196))
    # a tick is given (0.04 + 0.31) / 100 = 3.5 ms and runs 1.96 of them
    assert decode_wait_chip_share.read(obs) == pytest.approx(56.0)
    # two clocks over two spans: a share past 100 is not a number to give
    assert decode_wait_chip_share.read(dict(obs, trace=trace(0.0036))) is None
    assert decode_wait_chip_share.read(dict(obs, trace=trace(0.00343))) \
        == pytest.approx(98.0)
    # no trace (the CPU, ``--trace 0``) or no such program in it
    assert decode_wait_chip_share.read(dict(obs, trace=None)) is None
    assert decode_wait_chip_share.read(
        dict(obs, trace={"modules": {}})) is None


def test_first_token_delivery_ms_is_the_windows_mean():
    name = "serving_first_token_delivery_seconds"

    def reg(total, count):
        return {name: {"kind": "histogram", "help": "", "labelnames": (),
                       "samples": {(): hist(total, count)}}}
    obs = serving_obs(reg(0.5, 100), reg(0.5 + 0.8, 100 + 49))
    assert first_token_delivery_ms.read(obs) == pytest.approx(800.0 / 49)
    # no request streamed in the window
    assert first_token_delivery_ms.read(
        serving_obs(reg(0.5, 100), reg(0.5, 100))) is None


def test_dispatches_per_tick_and_stalled_s_are_the_windows_difference():
    start = {"tick_dispatches": 4000, "decode_ticks": 900,
             "slow_phases": 11, "slow_phase_s": 61.5}
    end = {"tick_dispatches": 4000 + 2600, "decode_ticks": 900 + 1000,
           "slow_phases": 12, "slow_phase_s": 61.5 + 5.61}
    obs = stats_obs(start, end)
    assert dispatches_per_tick.read(obs) == pytest.approx(2.6)
    assert stalled_s.read(obs) == pytest.approx(5.61)
    # a sound window reads 0, which is a number
    assert stalled_s.read(stats_obs(start, start)) == 0.0
    assert dispatches_per_tick.read(stats_obs(start, start)) is None


READERS = [decode_dispatch_ms, decode_wait_chip_share,
           first_token_delivery_ms, dispatches_per_tick, stalled_s]


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in READERS])
@pytest.mark.parametrize("obs", [
    {},                                              # a training run
    {"trace": None, "telemetry": None, "server_stats": None},
    # ``--trace 0``: the server's stats, no telemetry, no trace. The two
    # counter readers have their source there and are not asked
    {"trace": None, "telemetry": None,
     "server_stats": {"start": {}, "end": {}}},
], ids=["no-serving", "nothing", "no-telemetry"])
def test_a_missing_source_reads_as_none_not_an_error(reader, obs):
    assert reader.read(obs) is None


def test_the_parents_tree_gives_the_new_readers_nothing():
    """An observation of the parent's tree: telemetry and a trace, an
    undivided tick, stats without the stall's counters. Four readers find
    nothing. ``dispatches_per_tick`` divides two counters the parent already
    keeps (the sites are unchanged), so it reads the parent's number."""
    parent_stats = {"tick_dispatches": 260, "decode_ticks": 100}
    obs = dict(serving_obs(*phases(split=False)), trace=trace(0.00196),
               server_stats={"start": {k: 0 for k in parent_stats},
                             "end": parent_stats})
    for reader in (decode_dispatch_ms, decode_wait_chip_share,
                   first_token_delivery_ms, stalled_s):
        assert reader.read(obs) is None
    assert dispatches_per_tick.read(obs) == pytest.approx(2.6)


def test_host_share_reads_the_divided_tick_as_it_read_the_undivided():
    """The dispatch is the host's doing and still counts on the chip's side:
    ``tick_host_share`` lists the HOST phases by name, so a program's
    dispatch + wait weighs what its undivided wait weighed."""
    whole = serving_obs(*phases(split=False))
    halves = serving_obs(*phases(split=True))
    assert tick_host_share.read(halves) == pytest.approx(
        tick_host_share.read(whole))
    assert admit_ms_per_req.read(halves) == admit_ms_per_req.read(whole)


def test_one_registry_snapshot_of_a_served_wave_reads_as_the_parents_formula():
    """A stub server's own registry after a wave, every phase with a length
    (a clock that steps a millisecond a read): the two accepted readers
    against the formula written out, with a program's two phases summed
    where the parent had one."""
    from _serving_stub import StubModel
    from paddle_tpu.inference.continuous_batching import \
        ContinuousBatchingServer
    from paddle_tpu.telemetry import FakeClock, ServerTelemetry

    class Stepping(FakeClock):
        __slots__ = ()

        def now(self):
            t = super().now()
            self.advance(0.001)
            return t

    tele = ServerTelemetry(clock=Stepping())
    srv = ContinuousBatchingServer(StubModel(), max_slots=2, max_cache_len=32,
                                   cache_backend="paged", page_size=4,
                                   telemetry=tele)
    for prompt in ((1, 2, 3), (4, 5, 6, 7, 8), (9, 10)):
        srv.submit(np.asarray(prompt, np.int32), max_new_tokens=5)
    srv.run()
    snap = tele.registry.snapshot()
    obs = {"window": {"t0": 0.0, "t1": 10.0},
           "requests": [{"due": 1.0 + i, "rid": i} for i in range(3)],
           "telemetry": {"start": {}, "end": snap, "queue_wait_s": {}}}
    by_phase = {labels[0]: s["sum"] for labels, s in
                snap["serving_tick_phase_seconds"]["samples"].items()}
    assert {"decode_dispatch", "decode_wait", "prefill_dispatch",
            "prefill_wait"} <= set(by_phase)
    chip = sum(by_phase[p] for p in ("prefill_dispatch", "prefill_wait",
                                     "decode_dispatch", "decode_wait"))
    host = sum(s for p, s in by_phase.items()
               if p in tick_host_share.HOST)
    assert host > 0 and chip > 0
    assert host + chip == pytest.approx(sum(by_phase.values()))
    assert tick_host_share.read(obs) == pytest.approx(
        100.0 * host / (host + chip))
    assert admit_ms_per_req.read(obs) == pytest.approx(
        by_phase["admit"] * 1e3 / 3)
    assert decode_dispatch_ms.read(obs) > 0.0
    assert first_token_delivery_ms.read(obs) is None   # nothing streamed


# ------------------------------------------------------ the pending entries
def test_pending_entries_are_sound_and_wait_for_the_last_place():
    """Each pending entry is absent from ``per_layer``, has the contract's
    form and a reader, names a layer the benchmark names, and moves an
    end-to-end metric of every cell it lists. They wait for a steadier
    reasoning cell, not for a place: an entry is found by its name, and one
    may be appended wherever the list then ends."""
    manifest = harness.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [c["name"] for c in manifest["workloads"]]
    assert len(PENDING) == 5 and len(PENDING_LONGCTX) == 4
    assert [m["name"] for m in PENDING_REASON] == ["stalled_s.reason"]
    for m in PENDING + PENDING_LONGCTX + PENDING_REASON:
        assert m["name"] not in names
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert harness.load_module("layer_metrics", m["name"]).read
        # the layer is one the benchmark names, letter for letter
        assert m["layer"] in {e["layer"] for e in manifest["per_layer"]}
        # the metric it moves is reported in every cell where this one is
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells), m["name"]
    # between them the lists cover every serving cell with every reader
    for m in PENDING:
        also = [x for x in PENDING_LONGCTX + PENDING_REASON
                if x["name"] in (m["name"] + ".longctx",
                                 m["name"] + ".reason")]
        assert set(m["workloads"] + [c for x in also
                                     for c in x["workloads"]]) \
            == set(GAP_CELLS + [LONG])


# ------------------------------------------------------------- rehearsal
def test_rehearse_with_the_pending_entries(tmp_path):
    """The readers against the program's own spans and counters: a copy of the
    benchmark whose ``per_layer`` ends in ``PENDING``, as a ``benchmark`` PR
    would leave it. The CPU gives no device plane, so the share of the chip
    is left out there, not invented; the interpreter runs a kernel INSIDE
    the call that enqueues it on a chip, so a rehearsal's dispatches are long
    and ``stalled_s`` may well be over 0: no number here is a measurement."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest()
    manifest["per_layer"] += PENDING
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    line, text = rehearse(root, CHAT, 1)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    for m in PENDING:
        if m["name"] == "decode_wait_chip_share":
            continue
        assert got[m["name"]]["unit"] == m["unit"]
        assert isinstance(got[m["name"]]["value"], float)
    assert got["decode_dispatch_ms"]["value"] > 0.0
    assert got["first_token_delivery_ms"]["value"] > 0.0
    assert 1.0 <= got["dispatches_per_tick"]["value"] <= 8.0
    assert got["stalled_s"]["value"] >= 0.0
    assert "decode_wait_chip_share: nothing to read, left out" in text
    # the accepted readers of the same histogram still report
    assert 0.0 < got["tick_host_share"]["value"] < 100.0
