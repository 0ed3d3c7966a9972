"""BENCHMARK.json against the files it names and the contract's limits, and
the harness's refusal of names that no file defines."""
import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import harness, traffic

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    # a full check with the full 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert manifest["command"][1].startswith(tuple(manifest["paths"]))
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names))


# These tests lie under ``paths``, so a later PR can add to the manifest but
# cannot edit them: they hold EVERY entry to the contract's form, and only the
# entries this benchmark began with to their values.
def test_configs_name_files_that_exist(manifest):
    used = {c["config"] for c in manifest["workloads"]}
    files = set()
    for entry in manifest["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["name"] in used
        assert entry["file"].startswith(tuple(
            p + "/" for p in manifest["paths"]))
        assert entry["file"] not in files
        files.add(entry["file"])
        config = harness.load_config(manifest, entry["name"])
        assert config["source"] == entry["source"]
        assert 1 <= len(entry["why"]) <= 200
        assert len(entry["reduced"]) <= 16
        for key in entry["reduced"]:        # no width may be reduced
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
        harness.load_module("families", config["family"])
        assert os.path.isfile(os.path.join(ROOT, config["reference"]))
        assert "rehearse" in config         # tiny widths for the CPU tests
    first = next(e for e in manifest["configs"] if e["name"] == "gpt2-medium")
    assert first["reduced"] == []           # as published, nothing cut


def test_published_widths(manifest):
    medium = harness.load_config(manifest, "gpt2-medium")
    assert (medium["n_layer"], medium["n_embd"], medium["n_head"]) == (
        24, 1024, 16)
    assert medium["n_positions"] == 1024 and medium["vocab_size"] == 50257
    assert medium["n_embd"] // medium["n_head"] == 64
    family = harness.load_module("families", medium["family"])
    assert family.vocab(medium) == 50304
    assert family.sizes(medium, rehearse=True)["n_embd"] < 128


def test_cells_name_configs_mixes_and_drivers(manifest):
    pairs = set()
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        harness.load_config(manifest, cell["config"])
        mix = traffic.load_mix(cell["traffic"])
        harness.load_module("drivers", mix["kind"])
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
    four = sum(1 for c in manifest["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_metrics_have_readers_and_sound_arrows(manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert set(e2e) >= {"ttft_p50_ms", "ttft_p75_ms", "itl_p95_ms",
                        "train_tok_s", "setup_s"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        harness.load_module("end_to_end", m["name"])
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        harness.load_module("layer_metrics", m["name"])
        layers.add(m["layer"])
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    assert layers >= {"load generator", "scheduler", "page pool",
                      "tick programs", "train step", "kernels", "device"}
    for cell in cells:                       # every cell reports enough
        assert len(harness.cell_metrics(manifest, "end_to_end", cell)) >= 2
        assert len(harness.cell_metrics(manifest, "per_layer", cell)) >= 1


def test_mix_parameters(manifest):
    chat = traffic.load_mix("chat-steady")
    assert chat["kind"] == "open_loop"
    assert chat["arrivals"]["process"] == "poisson"
    assert chat["server"] == {"max_slots": 32, "page_size": 16,
                              "max_cache_len": 1024, "num_pages": 2049}
    assert chat["drain_s"] == 10
    train = traffic.load_mix("pretrain-1k")
    assert (train["kind"], train["micro_batch"], train["seq_len"]) == (
        "train", 8, 1024)
    assert train["loss_tolerance"] == 5e-5 and train["loss_tolerance_why"]


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_every_metric_lists_cells_that_exist(manifest, section):
    cells = {c["name"] for c in manifest["workloads"]}
    for m in manifest[section]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m.get("workloads", True), m["name"]      # never an empty list


def test_peaks_table():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in v5e["source"]
    from paddle_tpu.telemetry.costs import DEVICE_PEAKS
    assert DEVICE_PEAKS["TPU v5 lite"] == (v5e["bf16_flops_per_s"],
                                           v5e["hbm_bytes_per_s"])
    with pytest.raises(harness.UnknownName, match="no peaks"):
        harness.load_peaks("cpu")


@pytest.mark.parametrize("package,name", [
    ("layer_metrics", "no_such_reader"), ("layer_metrics", "no_such.steady"),
    ("end_to_end", "no_such_metric"), ("drivers", "closed_loop"),
    ("families", "no_such_family"), ("layer_metrics", "../run"),
])
def test_unknown_names_are_refused(package, name):
    with pytest.raises(harness.UnknownName):
        harness.load_module(package, name)


def test_suffix_makes_a_variant_not_new_code():
    a = harness.load_module("layer_metrics", "copy_share.steady")
    b = harness.load_module("layer_metrics", "copy_share.some-later-cell")
    assert a is b


def test_unknown_workload_exits_before_anything_runs(manifest):
    with pytest.raises(harness.UnknownName, match="unknown workload"):
        harness.find_cell(manifest, "gpt2-medium.no-such-mix")
    with pytest.raises(harness.UnknownName, match="unknown configuration"):
        harness.load_config(manifest, "gpt5")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "gpt2-medium.no-such-mix", "--seed", "1"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2 and out.stdout == ""
    assert "unknown workload" in out.stderr


def test_no_accelerator_is_an_error_and_prints_no_result(manifest):
    """On this CPU the real command must fail, not fall back."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", manifest["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 4
    assert "needs 1 TPU chip" in out.stderr
    last = out.stdout.strip().splitlines()[-1]
    with pytest.raises(ValueError):
        json.loads(last)
