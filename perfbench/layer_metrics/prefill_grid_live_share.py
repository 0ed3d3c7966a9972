"""Kernels: of the steps the ragged prefill kernel's grid took over the window
(a layer at a time), the share that attended a page of a live query tile:
``100 x prefill_live_steps / prefill_grid_steps`` from ``srv.stats``, the
window's difference. A program without the counters, or a window without a
prefill launch through that kernel, has nothing to read."""
STEPS, LIVE = "prefill_grid_steps", "prefill_live_steps"


def read(obs):
    s = obs.get("server_stats")
    if not s or STEPS not in s["end"] or LIVE not in s["end"]:
        return None
    steps = s["end"][STEPS] - s["start"][STEPS]
    if steps <= 0:
        return None
    return 100.0 * (s["end"][LIVE] - s["start"][LIVE]) / steps
