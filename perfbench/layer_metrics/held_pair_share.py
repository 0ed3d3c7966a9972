"""Expert FFN: of the (row, expert) choices the live rows of the window's decode
ticks made over ALL the router's experts, the share that fell on an expert this
chip holds: ``100 x moe_pairs_held / moe_pairs_routed`` from ``srv.stats``, the
window's difference. 25 when the router spreads its choices evenly over the four
shares of a layer; what is not held here is another chip's to compute. A program
without the counters, or a window without a routed pair, has nothing to read."""
ROUTED, HELD = "moe_pairs_routed", "moe_pairs_held"


def read(obs):
    s = obs.get("server_stats")
    if not s or ROUTED not in s["end"] or HELD not in s["end"]:
        return None
    routed = s["end"][ROUTED] - s["start"][ROUTED]
    if routed <= 0:
        return None
    return 100.0 * (s["end"][HELD] - s["start"][HELD]) / routed
