"""75th percentile, over the requests due in the window, of the time from
when a request was DUE to its first token on the client's clock: the highest
percentile that keeps ten of a window's some fifty requests beyond it."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    return stats.percentile(stats.window_ttfts_ms(obs), 75)
