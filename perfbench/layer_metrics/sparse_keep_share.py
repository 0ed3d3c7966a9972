"""Sparse attention: of the keys in the contexts of the window's live decode
rows, the share the learned selection kept: ``attn_keys_selected /
attn_keys_context`` from ``srv.stats``, the window's difference. A reading of
100 says the cell never cut a key. A program without the counters, or a
window without a live decode row, has nothing to read."""
CONTEXT, SELECTED = "attn_keys_context", "attn_keys_selected"


def read(obs):
    s = obs.get("server_stats")
    if not s or CONTEXT not in s["end"] or SELECTED not in s["end"]:
        return None
    context = s["end"][CONTEXT] - s["start"][CONTEXT]
    if context <= 0:
        return None
    return 100.0 * (s["end"][SELECTED] - s["start"][SELECTED]) / context
