"""Tick programs: of the slot-chunks the window's prefill launches ran (one a
slot a launch), the share that began past a prompt's start and so read the
slot state an earlier launch left: ``100 x prefill_chunks_carried /
prefill_chunks`` from ``srv.stats``, the window's difference. A program
without the counters, or a window without a launch, has nothing to read."""
CHUNKS, CARRIED = "prefill_chunks", "prefill_chunks_carried"


def read(obs):
    s = obs.get("server_stats")
    if not s or CHUNKS not in s["end"] or CARRIED not in s["end"]:
        return None
    chunks = s["end"][CHUNKS] - s["start"][CHUNKS]
    if chunks <= 0:
        return None
    return 100.0 * (s["end"][CARRIED] - s["start"][CARRIED]) / chunks
