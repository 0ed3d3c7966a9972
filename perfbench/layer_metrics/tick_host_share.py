"""Scheduler: the share of the serve loop's wall, over the window, spent in
phases that leave the chip idle. From the server's own phase boundary
(``serving_tick_phase_seconds{phase}`` in its telemetry registry): the window's
difference of the sums. The chip works through the ``*_wait`` phases (a
program's dispatch to its value read back); ``idle_wait``, the loop's sleep
with nothing to do, is nobody's doing and is left out of both sides."""
PHASES = "serving_tick_phase_seconds"
HOST = ("expire", "admit", "prefill_pack", "activate", "grow", "state_push",
        "emit", "harvest", "callbacks")
LEFT_OUT = ("idle_wait",)


def window_sums(obs, metric):
    """{label values: (sum, count)} of a histogram of the server's registry,
    end of the window less its start; None where the run had no telemetry or
    the registry no such histogram."""
    tele = obs.get("telemetry")
    if not tele or metric not in (tele.get("end") or {}):
        return None
    start = (tele.get("start") or {}).get(metric, {}).get("samples", {})
    out = {}
    for labels, end in tele["end"][metric]["samples"].items():
        was = start.get(labels, {"sum": 0.0, "count": 0})
        out[labels] = (end["sum"] - was["sum"], end["count"] - was["count"])
    return out


def read(obs):
    sums = window_sums(obs, PHASES)
    if not sums:
        return None
    by_phase = {labels[0]: s for labels, (s, _) in sums.items()}
    total = sum(s for p, s in by_phase.items() if p not in LEFT_OUT)
    if total <= 0:
        return None
    return 100.0 * sum(by_phase.get(p, 0.0) for p in HOST) / total
