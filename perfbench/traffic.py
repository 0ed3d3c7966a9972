"""One general traffic generator. A traffic mix is a data file under
``perfbench/traffic/``; this module turns (file, seed, seconds) into the
inputs of a run, all of them made BEFORE the window.

Steadiness: the SIZES and ARRIVALS of a mix are drawn from ``POOL_SEED`` and
the number of requests, so every ``--seed`` offers the same work in the same
order. ``--seed`` draws the token contents (and the weights).

Keys of a serving mix (``kind`` ``open_loop``):

- ``arrivals``: ``{"process": "poisson", "rate_per_s": r}`` (exponential
  gaps, scaled so that the arrivals fill the window exactly).
- ``prompt_tokens`` / ``output_tokens``: a distribution, see ``draw``.

Keys of a training mix (``kind`` ``train``): ``micro_batch``, ``seq_len``.

A process or a distribution that no mix uses is not here: the PR that brings
the mix brings it.
"""
import json
import os

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")
POOL_SEED = 0


def load_mix(name, traffic_dir=TRAFFIC_DIR):
    path = os.path.join(traffic_dir, name + ".json")
    if not os.path.isfile(path):
        raise KeyError(f"unknown traffic mix {name!r}: no file {path}")
    with open(path) as f:
        return json.load(f)


def draw(spec, n, rng):
    """``n`` whole numbers from a distribution given as data:
    ``{"dist": "exponential", "mean": m, "min": lo, "max": hi}``: the shape
    that a published mean fixes with no further parameter, rounded and then
    clipped to ``[lo, hi]``."""
    if spec["dist"] != "exponential":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    out = np.rint(rng.exponential(spec["mean"], n))
    return np.clip(out, spec["min"], spec["max"]).astype(np.int64)


def bounds(spec):
    """(least, most) a distribution can give: what the warm-up sizes by."""
    return spec["min"], spec["max"]


def arrival_times(spec, seconds, rng):
    """Due times inside ``[0, seconds)``, ascending; the count is fixed by
    the rate and the window, not drawn."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    n = max(1, round(spec["rate_per_s"] * seconds))
    gaps = rng.exponential(1.0, n)
    # first request due at 0, the gaps after the last one close the window
    return (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())


def serving_schedule(mix, seed, seconds, vocab, rate_per_s=None):
    """The requests of one window: ``[{"due", "prompt" (int32 array),
    "max_new_tokens"}]``, ascending in ``due``. ``rate_per_s`` overrides the
    file's rate (the knee sweep)."""
    arrivals = dict(mix["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    pool = np.random.default_rng(POOL_SEED)
    due = arrival_times(arrivals, seconds, pool)
    prompt_len = draw(mix["prompt_tokens"], len(due), pool)
    output_len = draw(mix["output_tokens"], len(due), pool)
    rng = np.random.default_rng(int(seed))
    return [{"due": float(t),
             "prompt": rng.integers(0, vocab, int(p)).astype(np.int32),
             "max_new_tokens": int(o)}
            for t, p, o in zip(due, prompt_len, output_len)]


def train_batch(mix, seed, step, vocab):
    """The token ids of step ``step``: a new seeded batch every step."""
    rng = np.random.default_rng([int(seed), int(step)])
    return rng.integers(0, vocab, (mix["micro_batch"], mix["seq_len"])
                        ).astype(np.int32)
