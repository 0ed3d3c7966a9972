"""Scheduler: submit to admission, from the server's own telemetry: the
``request.queued`` spans, one value a request due in the window."""
from perfbench import stats


def read(obs):
    tele = obs.get("telemetry")
    if not tele or not tele["queue_wait_s"]:
        return None
    w = obs["window"]
    waits = [tele["queue_wait_s"][r["rid"]] * 1e3 for r in
             stats.due_in_window(obs["requests"], w["t0"], w["t1"])
             if r["rid"] in tele["queue_wait_s"]]
    return stats.percentile(waits, 90)
