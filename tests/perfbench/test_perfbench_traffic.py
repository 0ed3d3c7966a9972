"""The traffic generator: deterministic in the seed, inside its clips, and
the same work for every seed."""
import numpy as np
import pytest

from perfbench import traffic

VOCAB = 50304


@pytest.fixture(scope="module")
def mix():
    return traffic.load_mix("chat-steady")


def lens(schedule):
    return ([len(r["prompt"]) for r in schedule],
            [r["max_new_tokens"] for r in schedule],
            [r["due"] for r in schedule])


def test_same_seed_same_inputs(mix):
    a = traffic.serving_schedule(mix, 3_000_000_001, 30, VOCAB)
    b = traffic.serving_schedule(mix, 3_000_000_001, 30, VOCAB)
    assert lens(a) == lens(b)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


def test_other_seed_other_tokens_same_work(mix):
    a = traffic.serving_schedule(mix, 1, 30, VOCAB)
    b = traffic.serving_schedule(mix, 2, 30, VOCAB)
    assert lens(a) == lens(b)            # sizes and arrivals: POOL_SEED's
    assert not all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))


def test_clips_and_window(mix):
    sched = traffic.serving_schedule(mix, 7, 40, VOCAB)
    p, o, due = lens(sched)
    assert min(p) >= mix["prompt_tokens"]["min"]
    assert max(p) <= mix["prompt_tokens"]["max"]
    assert min(o) >= mix["output_tokens"]["min"]
    assert max(o) <= mix["output_tokens"]["max"]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40
    for r in sched:
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < VOCAB


def test_the_longest_request_fits_the_cache(mix):
    cache = mix["server"]["max_cache_len"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= cache
    small = {**mix, **mix["rehearse"]}
    assert small["prompt_tokens"]["max"] + small["output_tokens"]["max"] <= \
        small["server"]["max_cache_len"]


def test_request_count_is_fixed_by_rate_and_window(mix):
    n = len(traffic.serving_schedule(mix, 5, 40, VOCAB, rate_per_s=2.5))
    assert n == 100
    file_rate = mix["arrivals"]["rate_per_s"]
    assert len(traffic.serving_schedule(mix, 5, 50, VOCAB)) == round(
        file_rate * 50)


def test_the_rate_is_the_stated_share_of_the_knee(mix):
    knee = mix["knee"]
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        knee["share"] * knee["rate_per_s"])
    assert 0.75 <= knee["share"] <= 0.8          # about four fifths


def test_lengths_follow_the_named_source(mix):
    """The means are LMSYS-Chat-1M's (Table 1), and the file says so."""
    assert "LMSYS-Chat-1M" in mix["source"] and "2309.11998" in mix["source"]
    assert mix["prompt_tokens"]["mean"] == 69.5
    assert mix["output_tokens"]["mean"] == 214.5
    assert mix["assumed"]


@pytest.mark.parametrize("mean,lo,hi", [(69.5, 4, 512), (214.5, 2, 512),
                                        (8, 4, 16)])
def test_exponential_lengths(mean, lo, hi):
    spec = {"dist": "exponential", "mean": mean, "min": lo, "max": hi}
    out = traffic.draw(spec, 20000, np.random.default_rng(0))
    assert out.dtype == np.int64
    assert out.min() == lo and out.max() == hi          # the clips bind
    assert traffic.bounds(spec) == (lo, hi)
    # the mean of a clipped exponential: lo + m (exp(-lo/m) - exp(-hi/m))
    want = lo + mean * (np.exp(-lo / mean) - np.exp(-hi / mean))
    assert out.mean() == pytest.approx(want, rel=0.03)


def test_poisson_arrivals():
    t = traffic.arrival_times({"process": "poisson", "rate_per_s": 10}, 100,
                              np.random.default_rng(0))
    assert len(t) == 1000 and t[0] == 0.0 and t[-1] < 100
    gaps = np.diff(t)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


@pytest.mark.parametrize("what,spec", [
    ("distribution", {"dist": "zipf", "min": 1, "max": 2}),
    ("arrival process", {"process": "gamma", "rate_per_s": 1, "cv": 3}),
])
def test_what_no_mix_uses_is_refused(what, spec):
    with pytest.raises(ValueError, match="unknown " + what):
        if "dist" in spec:
            traffic.draw(spec, 3, np.random.default_rng(0))
        else:
            traffic.arrival_times(spec, 10, np.random.default_rng(0))


def test_train_batches():
    mix = traffic.load_mix("pretrain-1k")
    a = traffic.train_batch(mix, 2 ** 31 + 5, 1, VOCAB)
    assert a.shape == (mix["micro_batch"], mix["seq_len"])
    assert np.array_equal(a, traffic.train_batch(mix, 2 ** 31 + 5, 1, VOCAB))
    assert not np.array_equal(a, traffic.train_batch(mix, 2 ** 31 + 5, 2,
                                                     VOCAB))
    assert not np.array_equal(a, traffic.train_batch(mix, 6, 1, VOCAB))


def test_unknown_mix_is_refused():
    with pytest.raises(KeyError, match="unknown traffic mix"):
        traffic.load_mix("no-such-mix")


def test_the_warm_up_is_sized_from_the_mix(mix):
    """Prompts of 4-512 under a 1024-token tick: every take from 1 to 512,
    and waves up to the 32 slots."""
    from perfbench import serving
    widths, waves = serving.warm_shapes(mix)
    assert widths == [2, 4, 8, 16, 32, 64, 128, 256, 512]
    assert waves == list(range(1, 33))
