"""The cell ``trinity-mini-26b-a3b.mixed-steady``: its configuration held to
the catalog's widths, its mix to the cell's stated parameters, the needed-bytes
arithmetic of ``hbm_roofline_afmoe.decode`` on hand-made numbers, the two new
readers on hand-made observations, and a rehearsal of the cell on the CPU
(tiny widths, float32, a window of 16 keys under prompts of 10 to 60) with and
without ``--trace 1``, and once through ``perfbench/controls_afmoe.py``, whose
negative controls of the reference fail the same check."""
import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, needed_bytes, needed_bytes_afmoe, traffic
from perfbench.families import afmoe
from perfbench.layer_metrics import hbm_roofline_afmoe, window_keep_share
from test_perfbench_rehearse import check_contract, rehearse

ROOT = harness.ROOT
CELL = "trinity-mini-26b-a3b.mixed-steady"
CONFIG = "trinity-mini-26b-a3b"
TYPES32 = (["sliding_attention"] * 3 + ["full_attention"]) * 8


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def config(manifest):
    return harness.load_config(manifest, CONFIG)


# ---------------------------------------------------------- configuration
def test_config_keeps_the_catalogs_widths(config):
    """Every number of the catalog's entry for Trinity-Mini, under its own
    key, but the two that are cut."""
    want = {"global_attn_every_n_layers": 4, "head_dim": 128,
            "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "load_balance_coeff": 0.001,
            "max_position_embeddings": 131072, "model_type": "afmoe",
            "moe_intermediate_size": 1024, "mup_enabled": True,
            "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
            "num_expert_groups": 1, "num_experts": 128,
            "num_experts_per_tok": 8, "num_key_value_heads": 4,
            "num_limited_groups": 1, "num_shared_experts": 1,
            "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
            "route_norm": True, "route_scale": 2.826,
            "score_func": "sigmoid", "sliding_window": 2048,
            "tie_word_embeddings": False, "topk_group": 1,
            "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: config[k] for k in want} == want


def test_only_depth_is_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers",
                                                    "layer_types"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert config["published"] == {"num_hidden_layers": 32,
                                   "layer_types": TYPES32}
    # the first 6 of 32: the 2 dense layers, then one whole period
    # ``window full window window``, 3 : 1 as published
    assert config["num_hidden_layers"] == 6
    assert config["layer_types"] == TYPES32[:6]
    assert "8-stage pipeline" in config["deployment"]
    assumed = config["assumed"]
    assert assumed["dtype"] == "bfloat16"
    for key in ("rope_on_window_layers_only", "attention_output_gate",
                "four_norms", "mup_embedding_scale", "router_eps",
                "window_edge", "init"):
        assert assumed[key]
    assert "NON-zero" in assumed["init"]


def test_program_config_is_the_files(config):
    cfg = afmoe.program_config(config)
    assert cfg.num_hidden_layers == 6 and cfg.dtype == "bfloat16"
    assert list(cfg.layer_types) == TYPES32[:6]
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size,
            cfg.head_dim, cfg.sliding_window) == (128, 8, 1024, 128, 2048)
    assert cfg.router_score == "sigmoid" and not cfg.tie_word_embeddings
    assert cfg.embed_scale == pytest.approx(2048 ** 0.5)
    assert cfg.router_eps == 1e-20
    c = afmoe.sizes(config)
    assert (c["n_layer"], c["n_embd"]) == (1, 512)      # the full layer's
    tiny = afmoe.sizes(config, rehearse=True)
    assert (tiny["num_hidden_layers"], tiny["hidden_size"], tiny["dtype"],
            tiny["sliding_window"]) == (6, 64, "float32", 16)
    with pytest.raises(NotImplementedError, match="no train step"):
        afmoe.build_model(config, 1, rehearse=True, train=True)


def test_mix_holds_the_cells_parameters(manifest):
    mix = traffic.load_mix("mixed-steady")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"dist": "exponential", "mean": 6144,
                                    "min": 256, "max": 30720}
    assert mix["output_tokens"] == {"dist": "exponential", "mean": 256,
                                    "min": 16, "max": 1024}
    assert mix["server"] == {"max_slots": 32, "page_size": 16,
                             "max_cache_len": 32768, "num_pages": 65537,
                             "prefill_tokens_per_tick": 1024,
                             "admission": "reserve"}
    knee = mix["knee"]
    assert knee["share"] == 0.8
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["rate_per_s"], rel=1e-9)
    # the longest request fits a slot's table
    assert 30720 + 1024 <= mix["server"]["max_cache_len"]
    # the rehearsal's prompts cross its window of 16 keys
    assert mix["rehearse"]["prompt_tokens"]["max"] > 16


def test_cell_reports_its_metrics(manifest):
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed-steady", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, "end_to_end",
                                                   CELL)}
    assert {"ttft_p50_ms", "setup_s"} <= e2e
    layer = {m["name"]: m for m in harness.cell_metrics(manifest,
                                                        "per_layer", CELL)}
    assert {"window_keep_share", "hbm_roofline_afmoe.decode",
            "gen_lag_p95_ms", "ttft_p90_ms", "queue_wait_p90_ms",
            "preempt_per_100req", "moe_pad_row_share",
            "decode_tick_ms.longctx", "prefill_tick_ms.longctx",
            "copy_share.longctx", "mosaic_share.longctx",
            "device_idle.longctx"} <= set(layer)
    assert layer["window_keep_share"]["layer"] == "kernels"
    assert layer["hbm_roofline_afmoe.decode"]["layer"] == "tick programs"
    for name in ("window_keep_share", "hbm_roofline_afmoe.decode"):
        assert layer[name]["moves"] == "ttft_p50_ms"
        assert CELL in layer[name]["workloads"]
    # a per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in layer.values()} <= e2e


# ------------------------------------------------------ needed bytes
TOY = {"hidden_size": 4, "head_dim": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "num_hidden_layers": 4,
       "num_dense_layers": 1, "sliding_window": 5,
       "layer_types": ["sliding_attention", "full_attention",
                       "sliding_attention", "sliding_attention"],
       "intermediate_size": 6, "moe_intermediate_size": 5, "num_experts": 3,
       "vocab_size": 10}


def test_needed_bytes_by_hand():
    assert needed_bytes_afmoe.layer_counts(TOY) == (3, 1, 1, 3)
    # 4 layers of: four norms (16), q and gate 4x4 and o 4x4 (48), k and v
    # 4x2 (16), q/k gains 2x2 (4) = 84 each; 1 dense FFN 3x4x6 (72); 3
    # routers 4x3 and shared experts 3x4x5 (72 each); the head 4x10 and
    # the final norm 4 (44); in bf16; then 3 expert_bias of 3, float32
    assert needed_bytes_afmoe.fixed_bytes(TOY) == (
        4 * 84 + 72 + 3 * 72 + 44) * 2 + 3 * 3 * 4
    assert needed_bytes_afmoe.expert_bytes(TOY) == 3 * 4 * 5 * 2
    # a row at 7 keys: K and V of 1 head of 2 (8 B a key) at all 7 in the
    # full layer and at the window's 5 in each of the 3 window layers
    assert needed_bytes_afmoe.row_cache_bytes(TOY, 7) == 7 * 8 + 3 * 5 * 8
    assert needed_bytes_afmoe.row_cache_bytes(TOY, 3) == 4 * 3 * 8
    need = needed_bytes_afmoe.decode_needed_bytes(
        TOY, ticks=2, experts_touched=5, contexts=[3, 7])
    assert need == 2 * needed_bytes_afmoe.fixed_bytes(TOY) + 5 * 120 \
        + 96 + 176


def test_needed_bytes_of_the_cells_model(config):
    """The cut's planning numbers: 12,582,912 B a routed expert; 2,048 B
    of K and V a token a layer; 1.35 GB of non-expert weights and head."""
    assert needed_bytes_afmoe.layer_counts(config) == (5, 1, 2, 4)
    assert needed_bytes_afmoe.expert_bytes(config) == 12582912
    assert needed_bytes_afmoe.row_cache_bytes(config, 1) == 6 * 2048
    assert needed_bytes_afmoe.row_cache_bytes(config, 10000) \
        == (10000 + 5 * 2048) * 2048
    assert needed_bytes_afmoe.fixed_bytes(config) == pytest.approx(
        2 * 130.0e6 + 4 * 67.6e6 + 820e6, rel=0.002)


# ----------------------------------------------------------- the readers
def _obs(stats0, stats1, **more):
    return dict({"server_stats": {"start": stats0, "end": stats1}}, **more)


def test_window_keep_share_on_hand_made_stats():
    zero = {"attn_keys_window_context": 1000, "attn_keys_window_attended": 500}
    end = {"attn_keys_window_context": 1000 + 8000,
           "attn_keys_window_attended": 500 + 2000}
    assert window_keep_share.read(_obs(zero, end)) == 25.0
    # a program without the counters (the parent), or an idle window
    assert window_keep_share.read(_obs({}, {})) is None
    assert window_keep_share.read(_obs(zero, zero)) is None
    assert window_keep_share.read({}) is None


def test_hbm_roofline_reader(config):
    """Two decode ticks in a 1 s slice of a 10 s window: one request of
    5,000 prompt tokens whose 2nd and 3rd tokens arrive inside it and whose
    4th after it. The slice gets the window's experts a live decode row (96
    over 3 rows) times its own 2 rows."""
    window = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "traced_s": 1.0}
    requests = [{"prompt_tokens": 5000,
                 "token_times": [104.0, 104.6, 105.2, 105.9]}]
    stats0 = {"decode_ticks": 0, "moe_experts_touched": 0}
    stats1 = {"decode_ticks": 3, "moe_experts_touched": 3 * 32}
    trace = {"modules": {"jit_decode_tick": {"runs": 2, "total_s": 0.02,
                                             "median_s": 0.01}}}
    obs = _obs(stats0, stats1, window=window, requests=requests,
               trace=trace, peaks={"hbm_bytes_per_s": 819e9})
    need = needed_bytes_afmoe.decode_needed_bytes(config, 2, 64,
                                                  [5001, 5002])
    assert hbm_roofline_afmoe.read(obs) == pytest.approx(
        needed_bytes.roofline_percent(need, 0.02, 819e9))
    assert 0 < hbm_roofline_afmoe.read(obs) < 100
    # nothing to read: no trace, no such program, or a parent's stats
    assert hbm_roofline_afmoe.read(dict(obs, trace=None)) is None
    assert hbm_roofline_afmoe.read(dict(obs, trace={"modules": {}})) is None
    assert hbm_roofline_afmoe.read(dict(obs, server_stats={
        "start": {}, "end": {}})) is None


# ------------------------------------------------------------- rehearsal
# The interpreted kernels make a rehearsal's decode tick 0.3-0.4 s on an
# idle CPU and several times that beside other tests; the harness's window
# summary needs a token gap inside the window, so the window is 6 s (the
# mix's rehearsal drains for up to 120 s, and stops once all are done).
SECONDS = "6"


def test_rehearse_end_to_end():
    line, text = rehearse(ROOT, CELL, 0, seconds=SECONDS)
    check_contract(line, CELL, "end_to_end", ["ttft_p50_ms", "setup_s"])
    assert "the second pass built 0 executables" in text
    assert "its argmax" in text


def test_rehearse_traced():
    line, text = rehearse(ROOT, CELL, 1, seconds=SECONDS)
    check_contract(line, CELL, "per_layer",
                   ["gen_lag_p95_ms", "queue_wait_p90_ms",
                    "preempt_per_100req", "moe_pad_row_share"])
    # prompts of 10 to 60 tokens against a window of 16: where a decode
    # row of the window fell, its window layers attended part of it
    if "window_keep_share" in line["metrics"]:
        assert 0.0 < line["metrics"]["window_keep_share"]["value"] <= 100.0
    assert "hbm_roofline_afmoe.decode: nothing to read, left out" in text


def test_controls_fail_the_rehearsals_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               BENCH_RUN="7")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "controls_afmoe.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", SECONDS,
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True
    said = {ln.split(":")[0].split()[1]: ln.rsplit(": ", 1)[1]
            for ln in lines if ln.startswith("control ")}
    assert said == {"window_whole_context": "FAILS",
                    "rope_on_full_layer": "FAILS",
                    "no_output_gate": "FAILS",
                    "experts_by_score_alone": "FAILS",
                    # a few percent on a row's weights: reported, not held
                    "bias_weighs": said.get("bias_weighs"),
                    "leaves_4_bits": "FAILS"}
