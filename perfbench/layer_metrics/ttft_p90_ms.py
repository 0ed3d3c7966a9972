"""Scheduler: 90th percentile of the time from when a request was DUE to its
first token. A window of some fifty requests keeps five beyond it, too few to
hold a PR to: the tail is recorded here and the median is judged."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    return stats.percentile(stats.window_ttfts_ms(obs), 90)
