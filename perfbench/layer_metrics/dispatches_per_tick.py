"""Scheduler: host-to-device dispatches a decode tick, as the server's sites
count them (the tick's program, a prefill launch, the slot-state pushes, the
block-table sync; an activation's eager slice, ``argmax`` and read-back are not
among them): ``tick_dispatches`` / ``decode_ticks`` from ``srv.stats``, the
window's difference. A program without the counters, or a window without a
decode tick, has nothing to read."""
DISPATCHES, TICKS = "tick_dispatches", "decode_ticks"


def read(obs):
    s = obs.get("server_stats")
    if not s or DISPATCHES not in s["end"] or TICKS not in s["end"]:
        return None
    ticks = s["end"][TICKS] - s["start"].get(TICKS, 0)
    if ticks <= 0:
        return None
    return (s["end"][DISPATCHES] - s["start"].get(DISPATCHES, 0)) / ticks
