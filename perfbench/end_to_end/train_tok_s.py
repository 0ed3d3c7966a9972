"""Tokens of the optimizer steps that ended inside the window (each step's
loss read back, so it has really ended), over the window and the chips."""
from perfbench import stats


def read(obs):
    if "steps" not in obs:
        return None
    w = obs["window"]
    return stats.train_tokens_per_s_per_chip(obs["steps"], w["t0"], w["t1"],
                                             obs["chips"])
