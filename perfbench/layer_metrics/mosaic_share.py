"""Kernels: share of the device's busy time in ``custom-call`` operations
(the Pallas kernels). A stand-in: a roofline share needs kernel names."""
from perfbench import trace_reduce


def read(obs):
    if not obs.get("trace"):
        return None
    return trace_reduce.share_of_busy(obs["trace"], "mosaic")
