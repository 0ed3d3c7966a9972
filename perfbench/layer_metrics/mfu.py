"""Train step: model FLOPs (6N + 12 * layers * hidden * S a token, times
the tokens of a step) over the median DEVICE duration of the program that
took most device time, over the peak in ``perfbench/peaks.json``."""
from perfbench import stats, trace_reduce


def read(obs):
    if not obs.get("trace") or "train" not in obs:
        return None
    main = trace_reduce.main_module(obs["trace"])
    if main is None:
        return None
    t = obs["train"]
    return stats.mfu_percent(t["flops_per_token"] * t["tokens_per_step"],
                             main[1]["median_s"],
                             obs["peaks"]["bf16_flops_per_s"])
