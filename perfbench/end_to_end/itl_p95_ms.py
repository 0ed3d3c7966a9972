"""95th percentile of the gaps between successive tokens of one request, all
requests pooled, counting the gaps whose later token fell inside the
window."""
from perfbench import stats


def read(obs):
    if "requests" not in obs:
        return None
    w = obs["window"]
    return stats.percentile(
        stats.token_gaps_ms(obs["requests"], w["t0"], w["t1"]), 95)
