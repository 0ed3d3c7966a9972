"""The cell ``nemotron3-super-120b-a12b.reason-steady``: its configuration held
to the catalog's widths, its mix to ISSUE 34's parameters, the needed-bytes
arithmetic of ``hbm_roofline_nemotron_h.decode`` on hand-made numbers, its three
new readers on hand-made observations, and a rehearsal of the cell on the CPU
(tiny widths, float32) with and without ``--trace 1``.

The three readers have NO entry in ``BENCHMARK.json`` yet (``PENDING`` below):
an entry for this cell has it measured anew, so the entries wait for a
``benchmark`` PR that measures it so. The cell's weights are one draw for every
seed (``assumed.weights_seed``) and its ``ttft_p75_ms`` is recorded per layer,
not judged: that percentile sits on the step between requests served at once
and those behind a launch. Until then the cell reads the
shared readers through the lists of the ``.assist`` entries, and the last test
here runs the three in a copy of the benchmark. No test here pins a place in
``per_layer`` or in a ``workloads`` list: entries are found by name, and this
cell is held as a member of the lists it reads, so a later PR appends its
entries and its cell without editing this file."""
import json
import os
import shutil

import pytest

from perfbench import harness, needed_bytes, needed_bytes_nemotron_h, traffic
from perfbench.families import nemotron_h
from perfbench.layer_metrics import (held_experts_touched_share,
                                     held_pair_share,
                                     hbm_roofline_nemotron_h)
from test_perfbench_rehearse import check_contract, rehearse

ROOT = harness.ROOT
CELL = "nemotron3-super-120b-a12b.reason-steady"
CONFIG = "nemotron3-super-120b-a12b"
PATTERN88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
          "BF16/blob/main/config.json")
# what a ``benchmark`` PR appends to ``per_layer`` once the cell is steady
PENDING = [
    {"name": "hbm_roofline_nemotron_h.decode", "unit": "%",
     "better": "higher", "source": "device_trace", "layer": "tick programs",
     "moves": "itl_p95_ms", "workloads": [CELL]},
    {"name": "held_pair_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "expert FFN",
     "moves": "itl_p95_ms", "workloads": [CELL]},
    {"name": "held_experts_touched_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "expert FFN",
     "moves": "itl_p95_ms", "workloads": [CELL]},
]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def config(manifest):
    return harness.load_config(manifest, CONFIG)


# ---------------------------------------------------------- configuration
def test_config_keeps_the_catalogs_widths(config):
    assert (config["hidden_size"], config["expand"]) == (4096, 2)
    assert (config["mamba_num_heads"], config["mamba_head_dim"],
            config["ssm_state_size"], config["n_groups"],
            config["conv_kernel"], config["chunk_size"]) == (
        128, 64, 128, 8, 4, 128)
    assert config["use_conv_bias"] is True
    assert (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"]) == (32, 2, 128)
    assert (config["moe_intermediate_size"], config["moe_latent_size"],
            config["moe_shared_expert_intermediate_size"],
            config["n_shared_experts"]) == (2688, 1024, 5376, 1)
    assert (config["num_experts_per_tok"], config["norm_topk_prob"],
            config["routed_scaling_factor"], config["n_group"],
            config["topk_group"]) == (22, True, 5, 1, 1)
    assert config["mlp_hidden_act"] == "relu2"
    assert config["layer_norm_epsilon"] == 1e-5
    assert (config["time_step_min"], config["time_step_max"],
            config["time_step_floor"]) == (0.001, 0.1, 0.0001)
    assert config["tie_word_embeddings"] is False
    assert config["max_position_embeddings"] == 262144
    assert config["model_type"] == "nemotron_h"


def test_depth_experts_held_and_vocabulary_are_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert entry["source"] == config["source"] == SOURCE
    assert config["published"] == {
        "num_hidden_layers": 88, "hybrid_override_pattern": PATTERN88,
        "n_routed_experts": 512, "vocab_size": 131072}
    assert (PATTERN88.count("M"), PATTERN88.count("E"),
            PATTERN88.count("*")) == (40, 40, 8) and len(PATTERN88) == 88
    # the first 11 of the 88: one whole period, 5 : 5 : 1 = 40 : 40 : 8
    assert config["num_hidden_layers"] == 11
    assert config["hybrid_override_pattern"] == PATTERN88[:11] \
        == "MEMEMEM*EME"
    # the share: 128 of the router's 512, a quarter of the vocabulary; the
    # guide's floors (8 experts, an eighth of the vocabulary) hold
    assert (config["n_routed_experts"], config["router_experts"],
            config["held_first"]) == (128, 512, 0)
    assert config["vocab_size"] * 4 == 131072
    assert "4 chips" in config["deployment"] \
        and "8 such hosts" in config["deployment"]
    assumed = config["assumed"]
    assert assumed["dtype"] == "bfloat16"
    for key in ("expert_share", "no_positional_term", "latent_projections",
                "state_dtype", "mamba_layout", "router", "mtp", "init"):
        assert assumed[key]


def test_weights_are_one_draw_for_every_seed(config):
    """The configuration fixes the seed of its weights, so every ``--seed``
    serves the same model and only the tokens differ; a configuration that
    fixes none draws its weights from the run's seed."""
    from types import SimpleNamespace

    from perfbench import serving
    assert config["assumed"]["weights_seed"] == 0
    assert "--seed" in config["assumed"]["weights_seed_why"]
    for seed in (0, 7, 2 ** 31 + 7, 2 ** 40 + 3):
        assert serving.weights_seed(SimpleNamespace(
            config=config, seed=seed)) == 0
    gpt2 = harness.load_config(harness.load_manifest(), "gpt2-medium")
    assert "weights_seed" not in gpt2.get("assumed", {})
    assert serving.weights_seed(SimpleNamespace(config=gpt2, seed=2 ** 31 + 7)
                                ) == 2 ** 31 + 7


def test_program_config_is_the_files(config):
    cfg = nemotron_h.program_config(config)
    assert cfg.num_hidden_layers == 11 and cfg.dtype == "bfloat16"
    assert cfg.sublayers == ("ssm", "moe") * 3 + ("ssm", "attn", "moe",
                                                  "ssm", "moe")
    assert cfg.experts_held == (0, 128) and cfg.router_experts == 512
    assert (cfg.top_k, cfg.head_dim, cfg.conv_dim) == (22, 128, 10240)
    assert cfg.rope_theta is None and cfg.router_score == "sigmoid"
    c = nemotron_h.sizes(config)
    assert (c["n_layer"], c["n_embd"]) == (1, 256)      # the pool's
    tiny = nemotron_h.sizes(config, rehearse=True)
    assert (tiny["hybrid_override_pattern"], tiny["hidden_size"],
            tiny["dtype"]) == ("MEM*E", 64, "float32")
    assert (tiny["n_routed_experts"], tiny["router_experts"],
            tiny["num_experts_per_tok"]) == (8, 16, 3)
    with pytest.raises(NotImplementedError, match="no train step"):
        nemotron_h.build_model(config, 1, rehearse=True, train=True)


def test_mix_holds_the_issues_parameters(manifest):
    mix = traffic.load_mix("reason-steady")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"dist": "exponential", "mean": 512,
                                    "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"dist": "exponential", "mean": 512,
                                    "min": 16, "max": 1024}
    assert mix["drain_s"] == 20
    assert mix["server"] == {"max_slots": 64, "page_size": 16,
                             "max_cache_len": 4096, "num_pages": 16385,
                             "prefill_tokens_per_tick": 1024,
                             "admission": "reserve"}
    assert "No trace is claimed" in mix["assumed"]
    knee = mix["knee"]
    assert knee["share"] == 0.8
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["rate_per_s"], rel=1e-9)
    # a percentile as high as the 75th keeps ten requests beyond it
    assert round(mix["arrivals"]["rate_per_s"] * manifest["run_seconds"]) \
        >= 40
    # the longest request fits a slot's table
    assert 2048 + 1024 <= mix["server"]["max_cache_len"]


def test_cell_reports_what_the_issue_lists(manifest):
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-steady", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, "end_to_end",
                                                   CELL)}
    assert {"ttft_p50_ms", "itl_p95_ms", "setup_s"} <= e2e
    # the 75th percentile sits on the step between requests served at once
    # and those behind a launch: recorded per layer, not judged
    assert "ttft_p75_ms" not in e2e
    layer = {m["name"]: m for m in harness.cell_metrics(manifest,
                                                        "per_layer", CELL)}
    assert {"decode_tick_ms.assist", "prefill_tick_ms.assist",
            "tick_host_share.assist", "admit_ms_per_req.assist",
            "submit_lock_wait_ms.assist", "copy_share.assist",
            "mosaic_share.assist", "device_idle.assist",
            "decode_grid_live_share.assist",
            "gen_lag_p95_ms", "ttft_p90_ms", "queue_wait_p90_ms",
            "preempt_per_100req", "moe_pad_row_share",
            "state_carry_share", "ttft_p75_ms.reason"} == set(layer)
    # a per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in layer.values()} <= e2e
    # no pending entry in ``per_layer`` yet, and every entry the cell reads
    # names it in a list of its own: a member, wherever a later cell joins
    names = [m["name"] for m in manifest["per_layer"]]
    assert not {m["name"] for m in PENDING} & set(names)
    for m in layer.values():
        assert CELL in m["workloads"]
    for m in PENDING:
        assert harness.load_module("layer_metrics", m["name"]).read
        assert m["moves"] in e2e


# ------------------------------------------------------ needed bytes
TOY = {"hidden_size": 4, "hybrid_override_pattern": "ME*M",
       "mamba_num_heads": 2, "mamba_head_dim": 4, "n_groups": 1,
       "ssm_state_size": 3, "conv_kernel": 4, "num_attention_heads": 2,
       "head_dim": 2, "num_key_value_heads": 1, "router_experts": 6,
       "n_routed_experts": 2, "moe_latent_size": 3,
       "moe_intermediate_size": 5,
       "moe_shared_expert_intermediate_size": 7, "vocab_size": 10}


def test_needed_bytes_by_hand():
    assert needed_bytes_nemotron_h.layer_counts(TOY) == (2, 1, 1)
    assert needed_bytes_nemotron_h.conv_channels(TOY) == 8 + 2 * 3
    # the norm of 4 layers (16); 2 Mamba layers of in_proj 4 x (8 + 14 + 2),
    # out_proj 8 x 4, 4 taps and a bias of 14, a gain of 8 (206 each); 1
    # attention layer: q and o 4x4, k and v 4x2 (48); 1 expert layer: router
    # 4x6, two latent projections 4x3, the shared expert 2 x 4x7 (104); the
    # head 4x10 and the final norm 4 (44); in bf16; then float32: A_log, D
    # and dt_bias of 2 heads in 2 layers, the router's bias of 6
    assert needed_bytes_nemotron_h.fixed_bytes(TOY) == (
        16 + 2 * 206 + 48 + 104 + 44) * 2 + (2 * 3 * 2 + 6) * 4 == 1320
    assert needed_bytes_nemotron_h.expert_bytes(TOY) == 2 * 3 * 5 * 2
    # a row's state in one Mamba layer: a window of 3 rows of 14 in bf16 and
    # S of 2 x 4 x 3 float32, each read and written
    assert needed_bytes_nemotron_h.row_state_bytes(TOY) == 2 * (84 + 96)
    # a row at 7 keys: K and V of 1 head of 2 (8 B a key) in the ONE
    # attention layer, and its state in each of the 2 Mamba layers
    assert needed_bytes_nemotron_h.row_cache_bytes(TOY, 7) == 7 * 8 + 2 * 360
    need = needed_bytes_nemotron_h.decode_needed_bytes(
        TOY, ticks=2, experts_touched=5, contexts=[3, 9, 9])
    assert need == 2 * 1320 + 5 * 60 + (744 + 792 + 792)


def test_needed_bytes_of_the_cell_match_the_issue(config):
    """ISSUE 34's planning numbers: 11,010,048 B an expert; 1,024 B of K
    and V a token in the one attention layer; 42 MB of state read and written
    a live row; 1.98 GB of non-expert weights and head; 10.0 GB a tick at 45
    live rows touching 110 of the 128 held experts in each of 5 layers."""
    assert needed_bytes_nemotron_h.layer_counts(config) == (5, 1, 5)
    assert needed_bytes_nemotron_h.conv_channels(config) == 10240
    assert needed_bytes_nemotron_h.expert_bytes(config) == 11010048
    assert needed_bytes_nemotron_h.row_state_bytes(config) \
        == 2 * (3 * 10240 * 2 + 128 * 64 * 128 * 4) == 8511488
    assert needed_bytes_nemotron_h.row_cache_bytes(config, 768) \
        == 768 * 1024 + 5 * 8511488
    assert needed_bytes_nemotron_h.fixed_bytes(config) == 1981470208
    need = needed_bytes_nemotron_h.decode_needed_bytes(config, 1, 110 * 5,
                                                       [768] * 45)
    assert need == pytest.approx(10.0e9, rel=0.01)
    assert need / 819e9 == pytest.approx(12.2e-3, rel=0.02)


# ----------------------------------------------------------- the readers
def _obs(stats0, stats1, **more):
    return dict({"server_stats": {"start": stats0, "end": stats1}}, **more)


def test_counter_readers_on_hand_made_stats():
    zero = {"moe_experts_touched": 100, "decode_ticks": 7,
            "moe_pairs_routed": 1000, "moe_pairs_held": 300}
    end = {"moe_experts_touched": 100 + 960, "decode_ticks": 7 + 2,
           "moe_pairs_routed": 1000 + 8800, "moe_pairs_held": 300 + 2310}
    # of 8,800 choices 2,310 fell on a held expert
    assert held_pair_share.read(_obs(zero, end)) == 26.25
    # a tick of 5 expert layers x 128 held experts holds 640: two ticks read
    # 960 of 1,280; at the rehearsal's 2 layers x 8 held two ticks hold 32
    assert held_experts_touched_share.read(
        _obs(zero, end, peaks={"hbm_bytes_per_s": 819e9})) == 75.0
    assert held_experts_touched_share.read(
        _obs(zero, dict(end, moe_experts_touched=100 + 24))) == 75.0
    # a program without the counters (the parent), or an idle window
    parent = {"moe_experts_touched": 5, "decode_ticks": 9}
    for reader in (held_pair_share, held_experts_touched_share):
        assert reader.read(_obs({}, {})) is None
        assert reader.read(_obs(parent, parent)) is None
        assert reader.read(_obs(zero, zero)) is None
        assert reader.read({}) is None


def test_hbm_roofline_reader(config):
    """Two decode ticks in a 1 s slice of a 10 s window: one request of 600
    prompt tokens whose 2nd and 3rd tokens arrive inside it and whose 4th
    after it. The slice gets the window's held experts a live decode row
    (330 over 3 rows) times its own 2 rows."""
    window = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "traced_s": 1.0}
    requests = [{"prompt_tokens": 600,
                 "token_times": [104.0, 104.6, 105.2, 105.9]}]
    stats0 = {"decode_ticks": 0, "moe_experts_touched": 0}
    stats1 = {"decode_ticks": 3, "moe_experts_touched": 3 * 110}
    trace = {"modules": {"jit_decode_tick": {"runs": 2, "total_s": 0.02,
                                             "median_s": 0.01}}}
    obs = _obs(stats0, stats1, window=window, requests=requests,
               trace=trace, peaks={"hbm_bytes_per_s": 819e9})
    need = needed_bytes_nemotron_h.decode_needed_bytes(config, 2, 220,
                                                       [601, 602])
    assert need == 2 * 1981470208 + 220 * 11010048 \
        + (601 + 602) * 1024 + 2 * 5 * 8511488
    assert hbm_roofline_nemotron_h.read(obs) == pytest.approx(
        needed_bytes.roofline_percent(need, 0.02, 819e9))
    assert 0 < hbm_roofline_nemotron_h.read(obs) < 100
    # nothing to read: no trace, no such program, or a parent's stats
    assert hbm_roofline_nemotron_h.read(dict(obs, trace=None)) is None
    assert hbm_roofline_nemotron_h.read(
        dict(obs, trace={"modules": {}})) is None
    assert hbm_roofline_nemotron_h.read(dict(obs, server_stats={
        "start": {}, "end": {}})) is None


# ------------------------------------------------------------- rehearsal
def test_rehearse_end_to_end():
    line, text = rehearse(ROOT, CELL, 0)
    check_contract(line, CELL, "end_to_end",
                   ["ttft_p50_ms", "itl_p95_ms", "setup_s"])
    assert "the second pass built 0 executables" in text
    assert "its argmax" in text


def test_rehearse_traced():
    line, text = rehearse(ROOT, CELL, 1, seconds="5")
    check_contract(line, CELL, "per_layer",
                   ["gen_lag_p95_ms", "queue_wait_p90_ms",
                    "tick_host_share.assist", "admit_ms_per_req.assist",
                    "moe_pad_row_share", "state_carry_share",
                    "ttft_p75_ms.reason"])
    assert 0.0 <= line["metrics"]["state_carry_share"]["value"] < 100.0
    assert "decode_tick_ms.assist: nothing to read, left out" in text


def test_rehearse_with_the_pending_entries(tmp_path):
    """The three readers against the program's own counters: a copy of the
    benchmark whose ``per_layer`` ends in ``PENDING``, as a ``benchmark`` PR
    would leave it."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest()
    manifest["per_layer"] += PENDING
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    line, text = rehearse(root, CELL, 1, seconds="5")
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    if "held_pair_share" in metrics:                  # a tick in the window
        # 8 of the router's 16 experts are held: about half of the choices
        assert 20.0 < metrics["held_pair_share"]["value"] < 80.0
        assert 0.0 < metrics["held_experts_touched_share"]["value"] <= 100.0
    assert "hbm_roofline_nemotron_h.decode: nothing to read, left out" in text
