"""The cell ``lfm2-24b-a2b.assist-steady``: its configuration held to the
catalog's widths, its mix to ISSUE 31's parameters, the needed-bytes
arithmetic of ``hbm_roofline_lfm2.decode`` on hand-made numbers, its counter
readers on hand-made observations, and a rehearsal of the cell on the CPU
(tiny widths, float32) with and without ``--trace 1``."""
import pytest

from perfbench import harness, needed_bytes, needed_bytes_lfm2, traffic
from perfbench.families import lfm2_moe
from perfbench.layer_metrics import (experts_touched_share,
                                     hbm_roofline_lfm2, state_carry_share)
from test_perfbench_rehearse import check_contract, rehearse

ROOT = harness.ROOT
CELL = "lfm2-24b-a2b.assist-steady"
CONFIG = "lfm2-24b-a2b"
TYPES40 = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 \
    + ["full_attention", "conv"]


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def config(manifest):
    return harness.load_config(manifest, CONFIG)


# ---------------------------------------------------------- configuration
def test_config_keeps_the_catalogs_widths(config):
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"]) == (2048, 11776, 1536)
    assert (config["num_attention_heads"],
            config["num_key_value_heads"]) == (32, 8)
    assert (config["num_experts"], config["num_experts_per_tok"],
            config["num_dense_layers"]) == (64, 4, 2)
    assert (config["conv_L_cache"], config["conv_bias"]) == (3, False)
    assert config["norm_eps"] == 1e-5
    assert config["norm_topk_prob"] is True
    assert config["use_expert_bias"] is True
    assert config["routed_scaling_factor"] == 1
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert (config["vocab_size"], config["max_position_embeddings"]) == (
        65536, 128000)
    assert config["model_type"] == "lfm2_moe"


def test_only_depth_is_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers",
                                                    "layer_types"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert config["num_hidden_layers"] == 10
    # the first 10 of the published 40: 2 dense conv layers, then two whole
    # periods, attention to conv 2 : 6 = the published 1 : 3
    assert config["published"] == {"num_hidden_layers": 40,
                                   "layer_types": TYPES40}
    assert config["layer_types"] == TYPES40[:10]
    assert TYPES40.count("full_attention") == 10
    assert "4-stage pipeline" in config["deployment"]
    assumed = config["assumed"]
    assert assumed["dtype"] == "bfloat16"
    assert assumed["tie_word_embeddings"] is True
    for key in ("tie_word_embeddings_why", "conv_weight_layout", "qk_norm",
                "other_modalities", "init", "init_scale_why"):
        assert assumed[key]
    assert set(assumed["init_scale"]) <= {
        "model.moe_layers.experts_w2", "model.dense_layers.w2",
        "model.moe_layers.router", "model.moe_layers.expert_bias"}


def test_program_config_is_the_files(config):
    cfg = lfm2_moe.program_config(config)
    assert cfg.num_hidden_layers == 10 and cfg.dtype == "bfloat16"
    assert list(cfg.layer_types) == TYPES40[:10]
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size,
            cfg.head_dim) == (64, 4, 1536, 64)
    assert cfg.router_score == "sigmoid" and cfg.tie_word_embeddings
    c = lfm2_moe.sizes(config)
    assert (c["n_layer"], c["n_embd"]) == (2, 512)      # the pool's
    tiny = lfm2_moe.sizes(config, rehearse=True)
    assert (tiny["num_hidden_layers"], tiny["hidden_size"],
            tiny["dtype"]) == (6, 64, "float32")
    with pytest.raises(NotImplementedError, match="no train step"):
        lfm2_moe.build_model(config, 1, rehearse=True, train=True)


def test_mix_holds_the_issues_parameters(manifest):
    mix = traffic.load_mix("assist-steady")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"dist": "exponential", "mean": 1155,
                                    "min": 64, "max": 3072}
    assert mix["output_tokens"] == {"dist": "exponential", "mean": 211,
                                    "min": 8, "max": 1024}
    assert mix["drain_s"] == 10
    assert mix["server"] == {"max_slots": 64, "page_size": 16,
                             "max_cache_len": 4096, "num_pages": 16385,
                             "prefill_tokens_per_tick": 1024,
                             "admission": "reserve"}
    assert "recalled" in mix["assumed"] and "Azure" in mix["source"]
    knee = mix["knee"]
    assert knee["share"] == 0.8
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["rate_per_s"], rel=1e-9)
    # ttft_p75_ms is judged only where ten requests lie beyond it
    assert round(mix["arrivals"]["rate_per_s"] * manifest["run_seconds"]) \
        >= 40
    # the longest request fits a slot's table
    assert 3072 + 1024 <= mix["server"]["max_cache_len"]


def test_cell_reports_what_the_issue_lists(manifest):
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "assist-steady", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, "end_to_end",
                                                   CELL)}
    assert {"ttft_p50_ms", "ttft_p75_ms", "setup_s"} <= e2e
    layer = {m["name"]: m for m in harness.cell_metrics(manifest,
                                                        "per_layer", CELL)}
    assert {"experts_touched_share", "state_carry_share",
            "hbm_roofline_lfm2.decode", "decode_tick_ms.assist",
            "prefill_tick_ms.assist", "tick_host_share.assist",
            "admit_ms_per_req.assist", "submit_lock_wait_ms.assist",
            "copy_share.assist", "mosaic_share.assist",
            "device_idle.assist", "gen_lag_p95_ms", "ttft_p90_ms",
            "queue_wait_p90_ms", "preempt_per_100req",
            "moe_pad_row_share"} <= set(layer)
    assert layer["experts_touched_share"]["layer"] == "expert FFN"
    assert layer["state_carry_share"]["layer"] == "tick programs"
    assert layer["hbm_roofline_lfm2.decode"]["layer"] == "tick programs"
    # a per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in layer.values()} <= e2e


# ------------------------------------------------------ needed bytes
TOY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "num_hidden_layers": 4, "num_dense_layers": 1,
       "layer_types": ["conv", "full_attention", "conv", "conv"],
       "intermediate_size": 6, "moe_intermediate_size": 5, "num_experts": 3,
       "conv_L_cache": 3, "vocab_size": 10}


def test_needed_bytes_by_hand():
    assert needed_bytes_lfm2.layer_counts(TOY) == (1, 3, 1, 3)
    # both norms of 4 layers (32); 3 conv layers of in_proj 4x12, out_proj
    # 4x4 and 3 taps of 4 (76 each); 1 attention layer: q and o 4x4, k and v
    # 4x2, q/k gains 2x2 (52); 1 dense FFN 3x4x6 (72); 3 routers 4x3 (36);
    # the tied head 4x10 and the final norm 4 (44); in bf16; then the 3
    # layers' expert_bias, 3 float32 each
    assert needed_bytes_lfm2.fixed_bytes(TOY) == (
        32 + 3 * 76 + 52 + 72 + 36 + 44) * 2 + 3 * 3 * 4
    assert needed_bytes_lfm2.expert_bytes(TOY) == 3 * 4 * 5 * 2
    # a row at 7 keys: K and V of 1 head of 2 (8 B a key) in the ONE
    # attention layer, and 2 rows of 4 (16 B) of state in each conv layer
    assert needed_bytes_lfm2.row_cache_bytes(TOY, 7) == 7 * 8 + 3 * 16
    need = needed_bytes_lfm2.decode_needed_bytes(
        TOY, ticks=2, experts_touched=5, contexts=[3, 9, 9])
    assert need == 2 * 964 + 5 * 120 + (72 + 120 + 120)


def test_needed_bytes_of_the_cell_match_the_issue(config):
    """ISSUE 31's planning numbers: 18,874,368 B an expert; 4,096 B of K
    and V a token over the 2 attention layers; 0.87 GB of non-expert
    weights and head; 6.1 GB a tick at 16 live rows touching 41 of 64
    experts in each of 8 layers."""
    assert needed_bytes_lfm2.layer_counts(config) == (2, 8, 2, 8)
    assert needed_bytes_lfm2.expert_bytes(config) == 18874368
    state = 8 * 2 * 2048 * 2
    assert needed_bytes_lfm2.row_cache_bytes(config, 1) == 4096 + state
    assert needed_bytes_lfm2.row_cache_bytes(config, 1400) \
        == 1400 * 4096 + state
    assert needed_bytes_lfm2.fixed_bytes(config) == pytest.approx(0.87e9,
                                                                  rel=0.01)
    need = needed_bytes_lfm2.decode_needed_bytes(config, 1, 41 * 8,
                                                 [1400] * 16)
    assert need == pytest.approx(7.15e9, rel=0.02)
    assert need / 819e9 == pytest.approx(8.7e-3, rel=0.03)


# ----------------------------------------------------------- the readers
def _obs(stats0, stats1, **more):
    return dict({"server_stats": {"start": stats0, "end": stats1}}, **more)


def test_counter_readers_on_hand_made_stats():
    zero = {"moe_experts_touched": 100, "decode_ticks": 7,
            "prefill_chunks": 4, "prefill_chunks_carried": 1}
    end = {"moe_experts_touched": 100 + 384, "decode_ticks": 7 + 2,
           "prefill_chunks": 4 + 40, "prefill_chunks_carried": 1 + 14}
    # a tick of 8 expert layers x 64 experts holds 512: two ticks read 384
    # of 1,024; at the rehearsal's 4 layers x 8 experts two ticks hold 64
    assert experts_touched_share.read(
        _obs(zero, end, peaks={"hbm_bytes_per_s": 819e9})) == 37.5
    assert experts_touched_share.read(
        _obs(zero, dict(end, moe_experts_touched=100 + 24))) == 37.5
    assert state_carry_share.read(_obs(zero, end)) == 35.0
    # a program without the counters (the parent), or an idle window
    for reader in (experts_touched_share, state_carry_share):
        assert reader.read(_obs({}, {})) is None
        assert reader.read(_obs(zero, zero)) is None
        assert reader.read({}) is None


def test_hbm_roofline_reader(config):
    """Two decode ticks in a 1 s slice of a 10 s window: one request of
    1,200 prompt tokens whose 2nd and 3rd tokens arrive inside it and whose
    4th after it. The slice gets the window's experts a live decode row (96
    over 3 rows) times its own 2 rows."""
    window = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "traced_s": 1.0}
    requests = [{"prompt_tokens": 1200,
                 "token_times": [104.0, 104.6, 105.2, 105.9]}]
    stats0 = {"decode_ticks": 0, "moe_experts_touched": 0}
    stats1 = {"decode_ticks": 3, "moe_experts_touched": 3 * 32}
    trace = {"modules": {"jit_decode_tick": {"runs": 2, "total_s": 0.02,
                                             "median_s": 0.01}}}
    obs = _obs(stats0, stats1, window=window, requests=requests,
               trace=trace, peaks={"hbm_bytes_per_s": 819e9})
    need = needed_bytes_lfm2.decode_needed_bytes(config, 2, 64,
                                                 [1201, 1202])
    assert hbm_roofline_lfm2.read(obs) == pytest.approx(
        needed_bytes.roofline_percent(need, 0.02, 819e9))
    assert 0 < hbm_roofline_lfm2.read(obs) < 100
    # nothing to read: no trace, no such program, or a parent's stats
    assert hbm_roofline_lfm2.read(dict(obs, trace=None)) is None
    assert hbm_roofline_lfm2.read(dict(obs, trace={"modules": {}})) is None
    assert hbm_roofline_lfm2.read(dict(obs, server_stats={
        "start": {}, "end": {}})) is None


# ------------------------------------------------------------- rehearsal
def test_rehearse_end_to_end():
    line, text = rehearse(ROOT, CELL, 0)
    check_contract(line, CELL, "end_to_end",
                   ["ttft_p50_ms", "ttft_p75_ms", "setup_s"])
    assert "the second pass built 0 executables" in text
    assert "its argmax" in text


def test_rehearse_traced():
    line, text = rehearse(ROOT, CELL, 1, seconds="5")
    check_contract(line, CELL, "per_layer",
                   ["gen_lag_p95_ms", "queue_wait_p90_ms",
                    "tick_host_share.assist", "admit_ms_per_req.assist",
                    "moe_pad_row_share", "state_carry_share"])
    # prompts of up to 40 tokens at 16 a tick: the window's launches ran
    # slot-chunks, some of which may have carried state (on a shared CPU
    # the later chunks can fall into the drain, after the window)
    assert 0.0 <= line["metrics"]["state_carry_share"]["value"] < 100.0
    if "experts_touched_share" in line["metrics"]:    # a tick in the window
        assert 0.0 < line["metrics"]["experts_touched_share"]["value"] <= 100.0
    assert "hbm_roofline_lfm2.decode: nothing to read, left out" in text
