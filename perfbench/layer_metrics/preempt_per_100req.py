"""Page pool: preemptions in the window per hundred requests due in it
(``srv.stats["preemptions"]``, the window's difference)."""
from perfbench import stats


def read(obs):
    if "server_stats" not in obs:
        return None
    w = obs["window"]
    due = len(stats.due_in_window(obs["requests"], w["t0"], w["t1"]))
    if not due:
        return None
    s = obs["server_stats"]
    return 100.0 * (s["end"]["preemptions"] - s["start"]["preemptions"]) / due
