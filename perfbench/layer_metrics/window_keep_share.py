"""Kernels: of the keys in the contexts of the window's live decode rows in a
model's WINDOW layers, the share those layers attend: ``attn_keys_window_
attended / attn_keys_window_context`` from ``srv.stats``, the window's
difference (the host's count through ``paged_attention.window_start``, which
bounds the window layers' grid). A reading of 100 says the window cut no key.
A program without the counters (one with no window layer), or a window without
a live decode row, has nothing to read."""
CONTEXT, ATTENDED = "attn_keys_window_context", "attn_keys_window_attended"


def read(obs):
    s = obs.get("server_stats")
    if not s or CONTEXT not in s["end"] or ATTENDED not in s["end"]:
        return None
    context = s["end"][CONTEXT] - s["start"][CONTEXT]
    if context <= 0:
        return None
    return 100.0 * (s["end"][ATTENDED] - s["start"][ATTENDED]) / context
