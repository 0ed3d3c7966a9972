"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as NEW files and NEW entries, editing no file that is there. This
test does exactly that in a copy of the benchmark and runs the new cell."""
import json
import os
import shutil

from test_perfbench_rehearse import ROOT, rehearse

READER = '''"""Scheduler: requests admitted in the window."""


def read(obs):
    if "server_stats" not in obs:
        return None
    s = obs["server_stats"]
    return float(s["end"]["admissions"] - s["start"]["admissions"])
'''


def test_new_files_and_entries_alone_make_a_new_cell(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    with open(os.path.join(ROOT, "perfbench/configs/gpt2-medium.json")) as f:
        config = json.load(f)
    config.update(source="https://huggingface.co/openai-community/gpt2-large",
                  n_layer=36, n_embd=1280, n_head=20)
    config["rehearse"].update(n_layer=3, n_embd=48, n_head=3)
    with open(os.path.join(root, "perfbench/configs/gpt2-large.json"),
              "w") as f:
        json.dump(config, f)
    mix = {"kind": "open_loop",
           "arrivals": {"process": "poisson", "rate_per_s": 0.5},
           "prompt_tokens": {"dist": "exponential", "mean": 300, "min": 128,
                             "max": 768},
           "output_tokens": {"dist": "exponential", "mean": 20, "min": 8,
                             "max": 32},
           "drain_s": 20,
           "server": {"max_slots": 8, "page_size": 16, "max_cache_len": 1024},
           "rehearse": {
               "arrivals": {"process": "poisson", "rate_per_s": 1.5},
               "prompt_tokens": {"dist": "exponential", "mean": 12, "min": 9,
                                 "max": 17},
               "output_tokens": {"dist": "exponential", "mean": 3, "min": 3,
                                 "max": 3},
               "drain_s": 30,
               "server": {"max_slots": 2, "page_size": 8,
                          "max_cache_len": 64}}}
    with open(os.path.join(root, "perfbench/traffic/long-prompts.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "perfbench/layer_metrics/admitted.py"),
              "w") as f:
        f.write(READER)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = "gpt2-large.long-prompts"
    manifest["configs"].append({
        "name": "gpt2-large", "source": config["source"],
        "file": "perfbench/configs/gpt2-large.json", "reduced": [],
        "why": "a third width on the shared code"})
    manifest["workloads"].append({
        "name": cell, "config": "gpt2-large", "traffic": "long-prompts",
        "chips": 1, "why": "long prompts, short answers, eight slots"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "itl_p95_ms"):
            m["workloads"].append(cell)
    manifest["per_layer"].append({
        "name": "admitted.long", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    line, text = rehearse(root, cell, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["admitted.long"]["value"] >= 1
    assert "n_layer" not in text and "K and V pool of 17 pages" in text
    # and the cell's end-to-end line would carry these
    from perfbench import harness
    assert {m["name"] for m in harness.cell_metrics(
        manifest, "end_to_end", cell)} >= {"ttft_p50_ms", "itl_p95_ms",
                                           "setup_s"}

    for path, data in before.items():          # no file that was there moved
        with open(path, "rb") as fh:
            assert fh.read() == data, path
