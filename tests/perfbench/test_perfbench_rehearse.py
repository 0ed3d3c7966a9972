"""``run.py --rehearse``: each driver kind end to end at tiny widths on the
CPU. A rehearsal proves the harness, never the chip: nothing it prints is a
measurement, and the line says ``cpu``."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rehearse(root, workload, trace, seconds="3", seed="2147483659"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               BENCH_RUN="7")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", workload, "--seed", seed, "--seconds", seconds,
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0]
    return json.loads(lines[-1]), out.stdout


def check_contract(line, cell, section, want_metrics):
    """The line's form, the metrics this benchmark began with, and none
    that the manifest does not give the cell in that section (a later PR
    adds metrics to a cell and cannot edit this file)."""
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m["unit"] for m in json.load(f)[section]
                  if cell in m.get("workloads", [cell])}
    assert set(want_metrics) <= set(line["metrics"]) <= set(listed)
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"] == listed[name]
    assert line["compiles_in_window"] == 0


def test_open_loop_end_to_end():
    line, text = rehearse(ROOT, "gpt2-medium.chat-steady", 0)
    check_contract(line, "gpt2-medium.chat-steady", "end_to_end",
                   ["ttft_p50_ms", "ttft_p75_ms", "itl_p95_ms", "setup_s"])
    assert "window: TTFT ms p50/p75/p90/p95/max" in text
    assert "the second pass built 0 executables" in text
    assert "set-up: warm-up pass 1" in text and "set-up: model" in text


def test_open_loop_traced():
    """The per-layer line: host-side readers report, the trace's readers
    find no TPU plane on the CPU and are left out, not invented."""
    line, text = rehearse(ROOT, "gpt2-medium.chat-steady", 1)
    check_contract(line, "gpt2-medium.chat-steady", "per_layer",
                   ["gen_lag_p95_ms", "ttft_p90_ms", "queue_wait_p90_ms",
                    "preempt_per_100req"])
    assert line["metrics"]["preempt_per_100req"]["value"] == 0.0
    assert "copy_share.steady: nothing to read, left out" in text
    assert "busy_s" not in line["device"]
    assert "pages held at one time" in text
    assert "requests, " in text and "its argmax" in text


def test_train_end_to_end():
    # a window long enough for a few steps on a CPU that other tests share
    line, text = rehearse(ROOT, "gpt2-medium.pretrain", 0, seconds="10")
    check_contract(line, "gpt2-medium.pretrain", "end_to_end",
                   ["train_tok_s", "setup_s"])
    assert "the second step built 0 executables" in text
    assert "against the f32 reference's" in text
