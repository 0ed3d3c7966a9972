"""The cell ``keye-vl2-30b-a3b.longctx-steady``: its configuration held to
the catalog's widths, its mix to ISSUE 27's parameters, the needed-bytes
arithmetic of ``hbm_roofline.decode`` on hand-made numbers, its counter
readers on hand-made observations, and a rehearsal of the cell on the CPU
(tiny widths, float32) with and without ``--trace 1``."""
import json
import os

import pytest

from perfbench import harness, needed_bytes, serving, traffic
from perfbench.families import keye_vl2
from perfbench.layer_metrics import (hbm_roofline, moe_pad_row_share,
                                     sparse_keep_share)
from test_perfbench_rehearse import check_contract, rehearse

ROOT = harness.ROOT
CELL = "keye-vl2-30b-a3b.longctx-steady"
CONFIG = "keye-vl2-30b-a3b"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def config(manifest):
    return harness.load_config(manifest, CONFIG)


# ---------------------------------------------------------- configuration
def test_config_keeps_the_catalogs_widths(config):
    assert config["hidden_size"] == 2048
    assert (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"]) == (32, 4, 128)
    assert (config["num_experts"], config["num_local_experts"],
            config["num_experts_per_tok"],
            config["moe_intermediate_size"]) == (128, 128, 8, 768)
    sa = config["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["indexer_num_kv_heads"], sa["topk"]) == (16, 64, 1, 2048)
    assert config["vocab_size"] == 151936
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert config["rope_theta"] == 10000000
    assert config["rms_norm_eps"] == 1e-6
    assert config["norm_topk_prob"] is True
    assert config["tie_word_embeddings"] is False


def test_only_depth_is_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 48}
    assert "8-stage pipeline on a v5e-8" in config["deployment"]
    assert config["assumed"]["dtype"] == "bfloat16"
    for key in ("qk_norm", "indexer_inputs", "indexer_rope",
                "indexer_scale", "chunk_sizes", "vision_tower"):
        assert config["assumed"][key]


def test_program_config_is_the_files(config):
    cfg = keye_vl2.program_config(config)
    assert cfg.num_hidden_layers == 6 and cfg.dtype == "bfloat16"
    assert cfg.indexer == (16, 64, 2048)
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size) == (128, 8,
                                                                    768)
    c = keye_vl2.sizes(config)
    assert (c["n_layer"], c["n_embd"]) == (6, 2048)
    tiny = keye_vl2.sizes(config, rehearse=True)
    assert (tiny["n_layer"], tiny["n_embd"], tiny["dtype"]) == (2, 64,
                                                                "float32")


def test_mix_holds_the_issues_parameters():
    mix = traffic.load_mix("longctx-steady")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"dist": "exponential", "mean": 6144,
                                    "min": 3072, "max": 14336}
    assert mix["output_tokens"] == {"dist": "exponential", "mean": 192,
                                    "min": 16, "max": 768}
    assert mix["drain_s"] == 10
    assert mix["server"] == {"max_slots": 8, "page_size": 16,
                             "max_cache_len": 16384, "num_pages": 8193,
                             "prefill_tokens_per_tick": 1024}
    # the rate is 0.8 of the swept knee, and the knee a point of the
    # sweep's grid (a request more or less in its 75 s window)
    knee = mix["knee"]
    assert knee["share"] == 0.8
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["rate_per_s"], rel=1e-9)
    assert knee["rate_per_s"] in (0.2, 0.2133, 0.2267, 0.24)
    # ttft_p75_ms is judged only where ten requests lie beyond it
    due = round(mix["arrivals"]["rate_per_s"] * 51)
    reports_p75 = CELL in next(
        m for m in harness.load_manifest()["end_to_end"]
        if m["name"] == "ttft_p75_ms")["workloads"]
    assert reports_p75 == (due >= 40)


def test_schedule_leaves_work_in_the_traced_slice():
    """The harness profiles the window's middle 3 s whatever the schedule
    (``serving.run_window``), and ``decode_tick_ms.longctx`` and
    ``hbm_roofline.decode`` read decode ticks there. The ten requests of
    0.192 req/s left that slice idle and the check refused the cell; of
    these nine, one is due within 2 s before the slice and streams over
    300 tokens (6 s of ticks) through it."""
    mix = traffic.load_mix("longctx-steady")
    schedule = traffic.serving_schedule(mix, 1, 51.0, 1000)
    assert len(schedule) == 9
    lo = (51.0 - serving.TRACE_SLICE_S) / 2.0
    assert any(lo - 2.0 <= r["due"] <= lo + 1.0
               and r["max_new_tokens"] >= 300 for r in schedule)


def test_cell_reports_what_the_issue_lists(manifest):
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx-steady", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, "end_to_end",
                                                   CELL)}
    # itl_p95_ms is NOT judged here (PERF.md sections 4 and 7): 4 to 5% of
    # this cell's token gaps hold a prefill launch, so the 95th percentile
    # sits on the edge of two modes and spreads past the admission's room
    assert e2e == {"ttft_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in harness.cell_metrics(manifest,
                                                        "per_layer", CELL)}
    assert {"hbm_roofline.decode", "moe_pad_row_share", "sparse_keep_share",
            "copy_share.longctx", "mosaic_share.longctx",
            "device_idle.longctx", "gen_lag_p95_ms", "ttft_p90_ms",
            "queue_wait_p90_ms", "preempt_per_100req",
            # the five tick entries list the chat cell alone, so this cell
            # reads them under entries of its own, same readers
            "decode_tick_ms.longctx", "prefill_tick_ms.longctx",
            "tick_host_share.longctx", "admit_ms_per_req.longctx",
            "submit_lock_wait_ms.longctx"} <= set(layer)
    assert layer["hbm_roofline.decode"]["layer"] == "tick programs"
    assert layer["moe_pad_row_share"]["layer"] == "expert FFN"
    assert layer["sparse_keep_share"]["layer"] == "sparse attention"
    # a per-layer metric moves an end-to-end metric its cell reports
    assert {m["moves"] for m in layer.values()} == {"ttft_p50_ms"}


# ------------------------------------------------------ needed bytes
TOY = {"hidden_size": 4, "head_dim": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "num_experts": 3,
       "moe_intermediate_size": 5, "num_hidden_layers": 2,
       "vocab_size": 10,
       "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 3,
                     "topk": 4}}


def test_needed_bytes_by_hand():
    # a layer outside its experts: q and o 4x4 each, k and v 4x2 each
    # (48); norms 2x4 + 2x2 (12); indexer 4 x (2x3 + 3 + 2) (44);
    # router 4x3 (12)
    assert needed_bytes.per_layer_fixed_params(TOY) == 48 + 12 + 44 + 12
    assert needed_bytes.expert_bytes(TOY) == 3 * 4 * 5 * 2
    # two layers, the head 4x10 and the final norm 4, in bf16
    assert needed_bytes.fixed_bytes(TOY) == (2 * 116 + 44) * 2
    # a row at 3 keys reads 3 K and V rows (1 head of 2, bf16: 8 B) and 3
    # indexer keys (3 dims: 6 B); at 9 keys 4 selected rows and 9 keys
    assert needed_bytes.row_cache_bytes(TOY, 3) == 3 * 8 + 3 * 6
    assert needed_bytes.row_cache_bytes(TOY, 9) == 4 * 8 + 9 * 6
    need = needed_bytes.decode_needed_bytes(TOY, ticks=2, experts_touched=5,
                                            contexts=[3, 9, 9])
    assert need == 2 * 552 + 5 * 120 + 2 * (42 + 86 + 86)
    assert needed_bytes.roofline_percent(1000, 0.5, 8000) == 25.0


def test_needed_bytes_of_the_cell_match_the_issue(config):
    """ISSUE 27's planning numbers: 9,437,184 B an expert; 2,048 B of K
    and V and 128 B of indexer key a position; non-expert weights 0.26 GB
    and the head 0.62 GB."""
    assert needed_bytes.expert_bytes(config) == 9437184
    assert needed_bytes.row_cache_bytes(config, 1) == 2048 + 128
    assert needed_bytes.row_cache_bytes(config, 10000) \
        == 2048 * 2048 + 10000 * 128
    assert 6 * needed_bytes.per_layer_fixed_params(config) * 2 \
        == pytest.approx(0.26e9, rel=0.03)
    assert needed_bytes.fixed_bytes(config) == pytest.approx(0.26e9 + 0.62e9,
                                                             rel=0.02)
    # a tick of 8 live rows touching 52 experts a layer at 6,000 keys
    need = needed_bytes.decode_needed_bytes(config, 1, 52 * 6, [6000] * 8)
    assert need / 819e9 == pytest.approx(4.7e-3, rel=0.08)


# ----------------------------------------------------------- the readers
def _obs(stats0, stats1, **more):
    return dict({"server_stats": {"start": stats0, "end": stats1}}, **more)


def test_counter_readers_on_hand_made_stats():
    zero = {"moe_rows": 10, "moe_live_rows": 10, "attn_keys_context": 0,
            "attn_keys_selected": 0}
    end = {"moe_rows": 110, "moe_live_rows": 35, "attn_keys_context": 8000,
           "attn_keys_selected": 2000}
    assert moe_pad_row_share.read(_obs(zero, end)) == 75.0
    assert sparse_keep_share.read(_obs(zero, end)) == 25.0
    # a program without the counters (the parent), or an idle window
    assert moe_pad_row_share.read(_obs({}, {})) is None
    assert sparse_keep_share.read(_obs({}, {})) is None
    assert moe_pad_row_share.read(_obs(zero, zero)) is None
    assert sparse_keep_share.read(_obs(zero, zero)) is None
    assert moe_pad_row_share.read({}) is None


def test_hbm_roofline_reader(config):
    """Two decode ticks in a 1 s slice of a 10 s window: one request of
    5,000 prompt tokens whose 2nd and 3rd tokens arrive inside it and
    whose 4th after it. The slice gets the window's experts a live decode
    row (144 over 3 rows) times its own 2 rows."""
    window = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "traced_s": 1.0}
    requests = [{"prompt_tokens": 5000,
                 "token_times": [104.0, 104.6, 105.2, 105.9]}]
    stats0 = {"decode_ticks": 0, "moe_experts_touched": 0}
    stats1 = {"decode_ticks": 3, "moe_experts_touched": 3 * 48}
    trace = {"modules": {"jit_decode_tick": {"runs": 2, "total_s": 0.02,
                                             "median_s": 0.01}}}
    obs = _obs(stats0, stats1, window=window, requests=requests,
               trace=trace, peaks={"hbm_bytes_per_s": 819e9})
    assert hbm_roofline.slice_contexts(obs) == [5001, 5002]
    need = needed_bytes.decode_needed_bytes(config, 2, 96, [5001, 5002])
    assert hbm_roofline.read(obs) == pytest.approx(
        100.0 * need / 0.02 / 819e9)
    assert 0 < hbm_roofline.read(obs) < 100
    # nothing to read: no trace, no such program, or a parent's stats
    assert hbm_roofline.read(dict(obs, trace=None)) is None
    assert hbm_roofline.read(dict(obs, trace={"modules": {}})) is None
    assert hbm_roofline.read(dict(obs, server_stats={
        "start": {}, "end": {}})) is None


# ------------------------------------------------------------- rehearsal
def test_rehearse_end_to_end():
    line, text = rehearse(ROOT, CELL, 0)
    check_contract(line, CELL, "end_to_end",
                   ["ttft_p50_ms", "setup_s"])
    assert "the second pass built 0 executables" in text
    assert "its argmax" in text


def test_rehearse_traced():
    line, text = rehearse(ROOT, CELL, 1)
    check_contract(line, CELL, "per_layer",
                   ["gen_lag_p95_ms", "queue_wait_p90_ms",
                    "tick_host_share.longctx", "admit_ms_per_req.longctx",
                    "moe_pad_row_share", "sparse_keep_share"])
    # contexts of 10 to 46 keys against the 8 the selection keeps
    assert 5.0 < line["metrics"]["sparse_keep_share"]["value"] < 60.0
    assert 0.0 < line["metrics"]["moe_pad_row_share"]["value"] < 100.0
    assert "hbm_roofline.decode: nothing to read, left out" in text
