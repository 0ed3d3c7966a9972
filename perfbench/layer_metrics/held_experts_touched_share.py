"""Expert FFN: of the HELD experts a decode tick could read (every held expert
of every expert layer, a tick), the share that a live row chose: ``100 x
moe_experts_touched / (decode_ticks x expert layers x experts held)`` from
``srv.stats``, the window's difference: what a tick reads of the experts'
weights, as a share of reading all it holds. The model's shapes are this cell's
configuration's (``obs`` names none), at the sizes the run served: the file's
own, or in a rehearsal (a run that was given no peaks) its ``rehearse`` block's.
A program without the counters (``moe_pairs_held`` marks one that counts the
touched experts among the held), or a window without a decode tick, has nothing
to read."""
from perfbench import harness, needed_bytes_nemotron_h
from perfbench.families import nemotron_h

CONFIG = "nemotron3-super-120b-a12b"
TOUCHED, TICKS, HELD = "moe_experts_touched", "decode_ticks", "moe_pairs_held"


def read(obs):
    s = obs.get("server_stats")
    if not s or any(k not in s["end"] for k in (TOUCHED, TICKS, HELD)):
        return None
    ticks = s["end"][TICKS] - s["start"][TICKS]
    if ticks <= 0:
        return None
    c = nemotron_h.sizes(harness.load_config(harness.load_manifest(), CONFIG),
                         rehearse=not obs.get("peaks"))
    held = (ticks * needed_bytes_nemotron_h.layer_counts(c)[2]
            * c["n_routed_experts"])
    return 100.0 * (s["end"][TOUCHED] - s["start"][TOUCHED]) / held
