"""Tick programs: the bytes the decode ticks of the traced slice NEEDED
(``perfbench/needed_bytes.py``) over the DEVICE seconds of ``jit_decode_tick``
in the slice, as a share of the chip's HBM bandwidth. The live rows and their
contexts are the client's: every token after a request's first that arrived
inside the slice was one live row of one tick, at a context of its prompt plus
the tokens before it. The experts touched are the server's counter
(``moe_experts_touched``), which ``obs`` has for the whole window only: the
slice is given the window's experts a live decode row times its own live
rows (the harness takes no snapshot of ``srv.stats`` at the slice's edges;
PERF.md section 7 asks a ``benchmark`` PR for one). A program without those
counters, or a trace without that program, has nothing to read. The model's
shapes are the configuration's of the one cell that reports this (``obs``
names no configuration): a second routed-expert configuration brings its
own entry."""
from perfbench import harness, needed_bytes

PROGRAM = "jit_decode_tick"
CONFIG = "keye-vl2-30b-a3b"
COUNTERS = ("decode_ticks", "moe_experts_touched")


def decode_contexts(obs, start, end):
    """Context of every live decode row whose token arrived in [start,
    end): a request's tokens after its first."""
    return [r["prompt_tokens"] + j
            for r in obs["requests"]
            for j, t in enumerate(r["token_times"]) if j and start <= t < end]


def slice_contexts(obs):
    w = obs["window"]
    start = w["t0"] + (w["seconds"] - w["traced_s"]) / 2.0
    return decode_contexts(obs, start, start + w["traced_s"])


def read(obs):
    trace, stats = obs.get("trace"), obs.get("server_stats")
    if not trace or PROGRAM not in trace["modules"] or not stats \
            or not obs.get("peaks") or not obs["window"].get("traced_s"):
        return None
    if any(k not in stats["end"] for k in COUNTERS):
        return None
    ticks, touched = (stats["end"][k] - stats["start"][k] for k in COUNTERS)
    module = trace["modules"][PROGRAM]
    if ticks <= 0 or module["total_s"] <= 0:
        return None
    w = obs["window"]
    window_rows = len(decode_contexts(obs, w["t0"], w["t1"]))
    contexts = slice_contexts(obs)
    if not window_rows:
        return None
    c = harness.load_config(harness.load_manifest(), CONFIG)
    need = needed_bytes.decode_needed_bytes(
        c, module["runs"], touched / window_rows * len(contexts), contexts)
    return needed_bytes.roofline_percent(need, module["total_s"],
                                         obs["peaks"]["hbm_bytes_per_s"])
