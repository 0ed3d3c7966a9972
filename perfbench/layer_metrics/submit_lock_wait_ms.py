"""Scheduler: the mean wait of ``submit()`` for the server's lock, which a
tick holds while it runs: ``serving_submit_lock_wait_seconds`` of the server's
telemetry registry, sum over count, the window's difference. This wait lies
between ``gen_lag_p95_ms`` (which ends where ``submit`` is called) and
``queue_wait_p90_ms`` (which begins once the lock is held)."""
from perfbench.layer_metrics import tick_host_share


def read(obs):
    sums = tick_host_share.window_sums(obs,
                                       "serving_submit_lock_wait_seconds")
    if not sums:
        return None
    total, count = sums[()]
    if not count:
        return None
    return total * 1e3 / count
