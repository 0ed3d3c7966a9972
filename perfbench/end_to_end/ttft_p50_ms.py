"""Median, over the requests due in the window, of the time from when a
request was DUE to its first token on the client's clock."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    return stats.percentile(stats.window_ttfts_ms(obs), 50)
