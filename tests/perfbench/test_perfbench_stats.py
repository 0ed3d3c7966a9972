"""The arithmetic of the metrics, on hand-made inputs."""
import math

import pytest

from perfbench import stats
from perfbench.end_to_end import (itl_p95_ms, setup_s, train_tok_s,
                                  ttft_p50_ms, ttft_p75_ms)
from perfbench.layer_metrics import (gen_lag_p95_ms, mfu, preempt_per_100req,
                                     queue_wait_p90_ms, ttft_p90_ms)


def req(due, times, prompt=10, budget=None, failed=False, sent=None, rid=0):
    budget = len(times) if budget is None else budget
    done = times[-1] if times and len(times) >= budget and not failed \
        else None
    return {"due": due, "sent": due if sent is None else sent, "rid": rid,
            "token_times": list(times), "prompt_tokens": prompt,
            "max_new_tokens": budget, "failed": failed, "done_at": done}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4, 5], 100, 5),
    ([10, 20], 25, 12.5), (list(range(101)), 90, 90),
    ([5], 95, 5), ([], 50, None),
    ([1, 2, math.inf], 100, math.inf),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_ttft_counts_from_due_and_the_missing_as_worst():
    window = {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "drain_s": 5.0}
    reqs = [req(100.0, [100.2, 100.3]),              # 200 ms
            req(101.0, [101.5]),                      # 500 ms
            req(102.0, []),                           # owes it: 13 s
            req(103.0, [103.1], failed=True),         # failed: 12 s
            req(99.0, [99.1]),                        # due before: out
            req(110.0, [110.1])]                      # due at t1: out
    got = sorted(stats.ttfts_ms(reqs, 100.0, 110.0, 115.0))
    assert got == pytest.approx([200.0, 500.0, 12000.0, 13000.0])
    obs = {"window": window, "requests": reqs}
    for reader, q in ((ttft_p50_ms, 50), (ttft_p75_ms, 75),
                      (ttft_p90_ms, 90)):
        assert reader.read(obs) == pytest.approx(
            stats.percentile([200.0, 500.0, 12000.0, 13000.0], q))
        assert reader.read({"steps": []}) is None


def test_token_gaps_count_when_the_later_token_is_inside():
    reqs = [req(0.0, [9.9, 10.1, 10.2, 20.5]),
            req(0.0, [10.0, 10.5])]
    got = sorted(stats.token_gaps_ms(reqs, 10.0, 20.0))
    assert got == pytest.approx([100.0, 200.0, 500.0])
    obs = {"window": {"t0": 10.0, "t1": 20.0}, "requests": reqs}
    assert itl_p95_ms.read(obs) == pytest.approx(
        stats.percentile([100.0, 200.0, 500.0], 95))


def test_peak_live_pages_counts_full_extents_between_first_and_last_token():
    from perfbench import serving
    reqs = [req(0, [1.0, 5.0], prompt=20, budget=12),       # 32 tokens: 2
            req(0, [2.0, 3.0, 6.0], prompt=1, budget=16),   # 17 tokens: 2
            req(0, [5.5, 7.0], prompt=100, budget=60),      # 160: 10
            req(0, [], prompt=500, budget=500)]             # never served
    # 1-2: 2 pages; 2-5: 4; 5-5.5: 2; 5.5-6: 12; 6-7: 10
    assert serving.peak_live_pages(reqs, 16) == 12
    assert serving.peak_live_pages(reqs[:2], 16) == 4
    assert serving.peak_live_pages([], 16) == 0


def ref_of(rows):
    """A reference whose logits are the given rows, whatever the ids."""
    import numpy as np
    return lambda ids: np.asarray(rows, np.float32)


@pytest.mark.parametrize("emitted,ok,agree", [
    ([2, 0], True, 2),          # both the argmax
    ([1, 0], True, 1),          # a near tie flipped: inside the margin
    ([0, 0], False, 1),         # a token far under the maximum
])
def test_check_tokens_margin_and_agreement(emitted, ok, agree):
    import numpy as np
    from perfbench import serving
    # rows predict the token AFTER each position; prompt of 2, so rows 1, 2
    # judge the two emitted tokens. Row 1: token 2 leads token 1 by 0.05 std
    row1 = np.array([-3.0, 0.95, 1.0, 0.0])
    row1[1] = row1[2] - 0.05 * row1.std()
    rows = [[0, 0, 0, 0], row1, [5.0, 0.0, 1.0, 2.0]]
    good, worst, same, line = serving.check_tokens(
        "r", ref_of(rows), np.array([7, 7], np.int32), emitted)
    assert good is ok and same == agree
    assert ("MISMATCH" in line) is (not ok)
    if emitted == [1, 0]:
        assert 0.0 < worst <= serving.LOGIT_MARGIN_STD
        assert worst == pytest.approx(0.05, rel=0.2)


def test_the_serving_check_is_tight():
    """About twice what the chip gave: PERF.md section 6 has the runs."""
    from perfbench import serving
    assert serving.LOGIT_MARGIN_STD <= 0.15
    assert serving.ARGMAX_AGREE_MIN >= 0.94
    assert serving.CHECKED_REQUESTS >= 12


def test_train_tokens_per_s_per_chip():
    steps = [{"end": t, "tokens": 8192, "loss": 1.0}
             for t in (0.5, 1.0, 1.5, 2.0, 2.5)]
    assert stats.train_tokens_per_s_per_chip(steps, 0.0, 2.0, 1) == 16384.0
    assert stats.train_tokens_per_s_per_chip(steps, 0.0, 2.0, 4) == 4096.0
    obs = {"window": {"t0": 0.0, "t1": 2.0}, "steps": steps, "chips": 1}
    assert train_tok_s.read(obs) == 16384.0
    assert setup_s.read({"setup_s": 12.5}) == 12.5


def test_mfu_arithmetic():
    # GPT-2 medium as built: 354.9M parameters, 24 layers, hidden 1024
    per_token = stats.transformer_train_flops_per_token(354_871_296, 24,
                                                        1024, 1024)
    assert per_token == 6 * 354_871_296 + 12 * 24 * 1024 * 1024
    assert stats.mfu_percent(197e12 * 0.5, 1.0, 197e12) == 50.0
    assert stats.mfu_percent(197e12, 1.0, 197e12, chips=4) == 25.0
    obs = {"trace": {"modules": {"jit_step": {"runs": 5, "total_s": 1.0,
                                              "median_s": 0.2},
                                 "jit_add": {"runs": 9, "total_s": 0.1,
                                             "median_s": 0.01}}},
           "train": {"flops_per_token": 2e9, "tokens_per_step": 8192},
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert mfu.read(obs) == pytest.approx(
        100 * 2e9 * 8192 / 0.2 / 197e12)
    assert mfu.read({"trace": None, "train": obs["train"]}) is None


def test_host_side_layer_readers():
    window = {"t0": 0.0, "t1": 10.0}
    reqs = [req(1.0, [2.0], sent=1.010, rid=1),
            req(2.0, [3.0], sent=2.030, rid=2),
            req(11.0, [12.0], sent=11.9, rid=3)]
    obs = {"window": window, "requests": reqs, "slots": 4,
           "server_stats": {"start": {"preemptions": 1},
                            "end": {"preemptions": 2}},
           "telemetry": {
               "queue_wait_s": {1: 0.1, 2: 0.3, 3: 9.0},
               "start": {}, "end": {}}}
    assert gen_lag_p95_ms.read(obs) == pytest.approx(
        stats.percentile([10.0, 30.0], 95))
    assert queue_wait_p90_ms.read(obs) == pytest.approx(
        stats.percentile([100.0, 300.0], 90))
    assert preempt_per_100req.read(obs) == pytest.approx(50.0)
    # a run without telemetry has nothing to read
    bare = dict(obs, telemetry=None)
    assert queue_wait_p90_ms.read(bare) is None
