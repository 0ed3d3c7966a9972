"""Tick programs: share of the device's busy time that is self time of
``copy``, ``dynamic-slice`` and ``dynamic-update-slice`` operations, fusions
of those included (layout copies and slice traffic around the page pool)."""
from perfbench import trace_reduce


def read(obs):
    if not obs.get("trace"):
        return None
    return trace_reduce.share_of_busy(obs["trace"], "copy")
