"""The five per-layer metrics that read what the serving tick says about
itself: two from the device trace by the tick programs' names, three from the
server's phase boundary and its lock timing. Each reader on hand-made
observations gives the number, and ``None`` where its source is missing (a
tree without the names or the histograms, a run without telemetry or trace);
the rehearsed chat cell prints the three that need no chip."""
import pytest

from perfbench import harness, trace_reduce
from perfbench.layer_metrics import (admit_ms_per_req, decode_tick_ms,
                                     prefill_tick_ms, submit_lock_wait_ms,
                                     tick_host_share)
from test_perfbench_rehearse import ROOT, rehearse

CELL = "gpt2-medium.chat-steady"
NEW = {"decode_tick_ms": ("ms", "device_trace", "tick programs",
                          "itl_p95_ms"),
       "prefill_tick_ms": ("ms", "device_trace", "tick programs",
                           "itl_p95_ms"),
       "tick_host_share": ("%", "program_span", "scheduler", "itl_p95_ms"),
       "admit_ms_per_req": ("ms/req", "program_span", "scheduler",
                            "ttft_p50_ms"),
       "submit_lock_wait_ms": ("ms", "program_span", "scheduler",
                               "ttft_p50_ms")}


def hist(total, count):
    return {"buckets": [("+Inf", count)], "sum": total, "count": count}


def registry(phases=None, lock=None):
    """A registry snapshot as ``MetricRegistry.snapshot()`` gives it."""
    snap = {}
    if phases is not None:
        snap["serving_tick_phase_seconds"] = {
            "kind": "histogram", "help": "", "labelnames": ("phase",),
            "samples": {(p,): hist(s, n) for p, (s, n) in phases.items()}}
    if lock is not None:
        snap["serving_submit_lock_wait_seconds"] = {
            "kind": "histogram", "help": "", "labelnames": (),
            "samples": {(): hist(*lock)}}
    return snap


def serving_obs(start, end, due=4):
    requests = [{"due": 10.0 + i, "rid": i} for i in range(due)]
    requests.append({"due": 99.0, "rid": 99})            # after the window
    return {"window": {"t0": 10.0, "t1": 20.0}, "requests": requests,
            "telemetry": {"start": start, "end": end, "queue_wait_s": {}}}


# warm-up left samples behind: only the window's difference may count
WARM = {"admit": (1.0, 50), "decode_wait": (5.0, 50), "idle_wait": (2.0, 9)}
END = {"expire": (0.01, 100), "admit": (1.0 + 0.2, 350),
       "prefill_pack": (0.03, 10), "prefill_wait": (1.5, 10),
       "activate": (0.06, 10), "state_push": (0.1, 100),
       "decode_wait": (5.0 + 6.5, 150), "emit": (0.2, 100),
       "harvest": (0.1, 200), "callbacks": (0.3, 100),
       "idle_wait": (2.0 + 3.0, 500)}


def test_tick_host_share_is_idle_phases_over_all_but_idle_wait():
    obs = serving_obs(registry(WARM), registry(END))
    host = 0.01 + 0.2 + 0.03 + 0.06 + 0.1 + 0.2 + 0.1 + 0.3
    chip = 1.5 + 6.5
    assert tick_host_share.read(obs) == pytest.approx(
        100.0 * host / (host + chip))
    # a phase name the reader does not know counts as not the host's
    odd = dict(END, fused_launch=(1.0, 5))
    assert tick_host_share.read(serving_obs(registry(WARM), registry(odd))) \
        == pytest.approx(100.0 * host / (host + chip + 1.0))


def test_admit_ms_per_req_divides_by_the_requests_due_in_the_window():
    obs = serving_obs(registry(WARM), registry(END), due=4)
    assert admit_ms_per_req.read(obs) == pytest.approx(0.2 * 1e3 / 4)


def test_submit_lock_wait_ms_is_the_windows_mean():
    obs = serving_obs(registry(lock=(0.5, 10)), registry(lock=(0.5 + 1.2, 40)))
    assert submit_lock_wait_ms.read(obs) == pytest.approx(1.2e3 / 30)
    # a histogram born inside the window has no start to subtract
    obs = serving_obs({}, registry(lock=(0.9, 3)))
    assert submit_lock_wait_ms.read(obs) == pytest.approx(300.0)


def device_rows():
    """A traced slice as ``trace_reduce.load_rows`` gives it: two decode
    ticks, two prefill launches of different widths, an eager scatter."""
    dev, ms = "/device:TPU:0", 1e6
    rows = [("/host:CPU", "python", trace_reduce.WINDOW_MARK, 0.0,
             1000 * ms)]
    modules = [("jit_decode_tick(7316451)", 0, 80), ("jit_prefill_tick(11)",
               100, 200), ("jit_decode_tick(7316451)", 320, 84),
               ("jit_prefill_tick(12)", 420, 340),
               ("jit_scatter(99)", 800, 1)]
    for name, start, dur in modules:
        rows.append((dev, trace_reduce.MODULES_LINE, name, start * ms,
                     dur * ms))
        rows.append((dev, trace_reduce.OPS_LINE,
                     "%paged_attention_decode.1 = bf16[32,16,64]{2,1,0} "
                     "custom-call(%a)", start * ms, dur * ms))
    return rows


def test_tick_programs_are_read_by_name_from_the_reduced_trace():
    obs = {"trace": trace_reduce.reduce_rows(device_rows())}
    assert set(obs["trace"]["modules"]) == {"jit_decode_tick",
                                            "jit_prefill_tick",
                                            "jit_scatter"}
    assert decode_tick_ms.read(obs) == pytest.approx(82.0)
    assert prefill_tick_ms.read(obs) == pytest.approx((200 + 340) / 2)
    # the kernel's own name is the instruction's, so the breakdown shows it
    assert obs["trace"]["device_ops"][0][0].startswith(
        "%paged_attention_decode.1")


READERS = [decode_tick_ms, prefill_tick_ms, tick_host_share,
           admit_ms_per_req, submit_lock_wait_ms]


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in READERS])
@pytest.mark.parametrize("obs", [
    {},                                          # a training run
    {"trace": None, "telemetry": None},          # --trace 0, or the CPU
    # the parent of this change: telemetry and a trace, but programs called
    # jit_run and a registry without the phase and lock histograms
    dict(serving_obs(registry(), registry()),
         trace={"modules": {"jit_run": {"runs": 21, "total_s": 2.7,
                                        "median_s": 0.0817}}}),
], ids=["no-serving", "untraced", "parent-tree"])
def test_a_missing_source_reads_as_none_not_an_error(reader, obs):
    assert reader.read(obs) is None


def test_manifest_entries_and_readers_agree():
    manifest = harness.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source, layer, moves) in NEW.items():
        m = entries[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"], m["workloads"]) == (
            unit, source, layer, moves, "lower", [CELL])
        assert harness.load_module("layer_metrics", name).read
    # appended after what the benchmark began with (a later PR appends
    # after these in turn, and cannot edit this file)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[10:15] == list(NEW)


@pytest.fixture(scope="module")
def traced_rehearsal():
    return rehearse(ROOT, CELL, 1)


def test_rehearsed_chat_cell_prints_the_program_span_metrics(
        traced_rehearsal):
    line, text = traced_rehearsal
    assert line["correct"] is True and line["compiles_in_window"] == 0
    got = line["metrics"]
    for name in ("tick_host_share", "admit_ms_per_req",
                 "submit_lock_wait_ms"):
        assert got[name]["unit"] == NEW[name][0]
        assert isinstance(got[name]["value"], float)
        assert got[name]["value"] >= 0.0
    assert 0.0 < got["tick_host_share"]["value"] < 100.0
    assert got["admit_ms_per_req"]["value"] > 0.0
    # the CPU gives no device plane: the two trace readers are left out
    for name in ("decode_tick_ms", "prefill_tick_ms"):
        assert name not in got
        assert f"{name}: nothing to read, left out" in text

