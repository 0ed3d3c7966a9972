"""The arithmetic behind every number the benchmark prints. Plain Python on
plain inputs, so the tests check it on hand-made values."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between the
    order statistics (numpy's default). An infinite value stays infinite:
    a request that never got its token is the worst, not a dropped sample."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_in_window(requests, t0, t1):
    return [r for r in requests if t0 <= r["due"] < t1]


def ttfts_ms(requests, t0, t1, end):
    """Time from DUE to the first token, in ms, for every request due inside
    ``[t0, t1)``. One that failed, was refused or still owed its first token
    at ``end`` (the end of the drain) counts as the worst it can be: from
    its due time to ``end``."""
    out = []
    for r in due_in_window(requests, t0, t1):
        worst = (end - r["due"]) * 1e3
        if r["failed"] or not r["token_times"]:
            out.append(worst)
        else:
            out.append(min(worst, (r["token_times"][0] - r["due"]) * 1e3))
    return out


def window_ttfts_ms(obs):
    """``ttfts_ms`` of a serving run's observations: the requests due in its
    window, the worst counted to the end of its drain."""
    w = obs["window"]
    return ttfts_ms(obs["requests"], w["t0"], w["t1"], w["t1"] + w["drain_s"])


def token_gaps_ms(requests, t0, t1):
    """Gaps between successive tokens of one request, all requests pooled,
    counting a gap when its LATER token falls inside ``[t0, t1)``."""
    out = []
    for r in requests:
        ts = r["token_times"]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    return out


def train_tokens_per_s_per_chip(steps, t0, t1, chips):
    """Tokens of the optimizer steps that ENDED inside the window, over the
    window and the chips."""
    done = sum(s["tokens"] for s in steps if t0 <= s["end"] <= t1)
    return done / (t1 - t0) / chips


def transformer_train_flops_per_token(n_params, layers, hidden, seq):
    """Forward plus backward of a dense decoder: 6 per parameter per token
    for the matrix multiplications with weights, plus attention's two
    products with the sequence, 12 * layers * hidden * seq (PaLM, appendix
    B; causal masking is NOT discounted, as there). Recomputed operations
    do not count."""
    return 6.0 * n_params + 12.0 * layers * hidden * seq


def mfu_percent(flops_per_step, step_seconds, peak_flops, chips=1):
    return 100.0 * flops_per_step / step_seconds / (peak_flops * chips)
