"""Scheduler: seconds of the window inside tick phases that lasted
``telemetry.serving.SLOW_PHASE_S`` (0.25 s) or longer, where an honest phase is
at most a launch of some 50 ms: ``slow_phase_s`` from ``srv.stats``, the
window's difference; 0 in a sound run. The server keeps a record of each such
phase (``srv.slow_phases``: phase, tick, the compiles and collector pauses that
ended inside it) and warns, traced or not. A program without the counter has
nothing to read."""
STALLED = "slow_phase_s"


def read(obs):
    s = obs.get("server_stats")
    if not s or STALLED not in s["end"]:
        return None
    return float(s["end"][STALLED] - s["start"].get(STALLED, 0.0))
