"""Scheduler: the mean time the host takes to build and enqueue a decode tick,
from the mark that opens ``decode_dispatch`` to the return of the program's
call: ``serving_tick_phase_seconds{phase="decode_dispatch"}`` of the server's
telemetry registry, sum over count, the window's difference. What follows it,
``decode_wait``, is the host waiting for the tokens. A tree whose tick does not
divide there has no such phase and nothing to read."""
from perfbench.layer_metrics import tick_host_share


def window_mean_ms(obs, metric, labels=()):
    """A histogram child's mean over the window, in ms: the difference of the
    sums over that of the counts; None without the metric, the child or a
    sample in the window."""
    sums = tick_host_share.window_sums(obs, metric)
    if not sums or labels not in sums:
        return None
    total, count = sums[labels]
    return total * 1e3 / count if count else None


def read(obs):
    return window_mean_ms(obs, tick_host_share.PHASES, ("decode_dispatch",))
