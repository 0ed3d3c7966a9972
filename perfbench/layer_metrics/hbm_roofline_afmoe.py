"""Tick programs: ``hbm_roofline.decode``'s reading for the ``afmoe``
configuration: the bytes the decode ticks of the traced slice NEEDED
(``perfbench/needed_bytes_afmoe.py``: non-expert weights and the untied head
once a tick, 12,582,912 B a routed expert touched, a live row's K and V at its
context in the full layer and at its window in the window layers) over the
DEVICE seconds of ``jit_decode_tick`` in the slice, as a share of the chip's
HBM bandwidth. Rows, contexts and the slice's share of the window's experts
are found as ``hbm_roofline`` finds them (its functions, not a copy). The
model's shapes are this cell's configuration's (``obs`` names none)."""
from perfbench import harness, needed_bytes, needed_bytes_afmoe
from perfbench.layer_metrics.hbm_roofline import (COUNTERS, PROGRAM,
                                                  decode_contexts,
                                                  slice_contexts)

CONFIG = "trinity-mini-26b-a3b"


def read(obs):
    trace, stats = obs.get("trace"), obs.get("server_stats")
    if not trace or PROGRAM not in trace["modules"] or not stats \
            or not obs.get("peaks") or not obs["window"].get("traced_s"):
        return None
    if any(k not in stats["end"] for k in COUNTERS):
        return None
    ticks, touched = (stats["end"][k] - stats["start"][k] for k in COUNTERS)
    module = trace["modules"][PROGRAM]
    w = obs["window"]
    window_rows = len(decode_contexts(obs, w["t0"], w["t1"]))
    if ticks <= 0 or module["total_s"] <= 0 or not window_rows:
        return None
    contexts = slice_contexts(obs)
    c = harness.load_config(harness.load_manifest(), CONFIG)
    need = needed_bytes_afmoe.decode_needed_bytes(
        c, module["runs"], touched / window_rows * len(contexts), contexts)
    return needed_bytes.roofline_percent(need, module["total_s"],
                                         obs["peaks"]["hbm_bytes_per_s"])
