"""Device: 1 minus the union of the device plane's ``XLA Ops`` intervals
over the traced window."""
from perfbench import trace_reduce


def read(obs):
    if not obs.get("trace"):
        return None
    return trace_reduce.idle_share(obs["trace"])
