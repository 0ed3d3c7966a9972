"""Scheduler: host time in the ``admit`` phase (pop, prefix match, eviction
scan, page reservation, binding; both passes of a tick) over the window, by
the requests due in it. The same histogram and difference as
``tick_host_share``."""
from perfbench import stats
from perfbench.layer_metrics import tick_host_share


def read(obs):
    sums = tick_host_share.window_sums(obs, tick_host_share.PHASES)
    if not sums or ("admit",) not in sums or "requests" not in obs:
        return None
    w = obs["window"]
    due = len(stats.due_in_window(obs["requests"], w["t0"], w["t1"]))
    if not due:
        return None
    return sums[("admit",)][0] * 1e3 / due
