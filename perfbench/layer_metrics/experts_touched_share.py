"""Expert FFN: of the experts a decode tick could read (every expert of every
expert layer, a tick), the share that a live row chose: ``100 x
moe_experts_touched / (decode_ticks x expert layers x experts)`` from
``srv.stats``, the window's difference: what a tick reads of the experts'
weights, as a share of holding them all. The model's shapes are this cell's
configuration's (``obs`` names none), at the sizes the run served: the file's
own, or in a rehearsal (a run that was given no peaks) its ``rehearse``
block's. A program without the counters, or a window without a decode tick,
has nothing to read."""
from perfbench import harness, needed_bytes_lfm2
from perfbench.families import lfm2_moe

CONFIG = "lfm2-24b-a2b"
TOUCHED, TICKS = "moe_experts_touched", "decode_ticks"


def read(obs):
    s = obs.get("server_stats")
    if not s or TOUCHED not in s["end"] or TICKS not in s["end"]:
        return None
    ticks = s["end"][TICKS] - s["start"][TICKS]
    if ticks <= 0:
        return None
    c = lfm2_moe.sizes(harness.load_config(harness.load_manifest(), CONFIG),
                       rehearse=not obs.get("peaks"))
    held = ticks * needed_bytes_lfm2.layer_counts(c)[3] * c["num_experts"]
    return 100.0 * (s["end"][TOUCHED] - s["start"][TOUCHED]) / held
