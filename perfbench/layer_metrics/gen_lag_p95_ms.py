"""Load generator: how late the generator's thread sent a request, against
when it was due. A starved generator must not read as a fast server."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    w = obs["window"]
    lags = [(r["sent"] - r["due"]) * 1e3 for r in stats.due_in_window(
        obs["requests"], w["t0"], w["t1"]) if r["sent"] is not None]
    return stats.percentile(lags, 95)
