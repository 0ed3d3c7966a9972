"""Expert FFN: of the rows the window's launches sent through the expert FFN
(a launch computes slots x width of them, whatever is live), the share that
belonged to no live token: ``1 - moe_live_rows / moe_rows`` from
``srv.stats``, the window's difference. A program without the counters, or a
window without a launch, has nothing to read."""
ROWS, LIVE = "moe_rows", "moe_live_rows"


def read(obs):
    s = obs.get("server_stats")
    if not s or ROWS not in s["end"] or LIVE not in s["end"]:
        return None
    rows = s["end"][ROWS] - s["start"][ROWS]
    if rows <= 0:
        return None
    return 100.0 * (1.0 - (s["end"][LIVE] - s["start"][LIVE]) / rows)
