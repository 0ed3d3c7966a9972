"""Scheduler: 75th percentile of the time from when a request was DUE to its
first token, in a cell where that percentile sits on the step between the
requests served at once and those behind a launch, so that it swings too
widely between runs to be judged end to end: it is recorded here and the
median is judged."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    return stats.percentile(stats.window_ttfts_ms(obs), 75)
