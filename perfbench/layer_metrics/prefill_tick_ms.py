"""Tick programs: the mean DEVICE duration of a ragged prefill launch in the
traced slice, whatever its chunk width (a slice holds a few launches of
different widths, so this is wide from run to run). The program is found by
the name the server gives it, ``jit_prefill_tick``."""
PROGRAM = "jit_prefill_tick"


def read(obs):
    trace = obs.get("trace")
    if not trace or PROGRAM not in trace["modules"]:
        return None
    runs = trace["modules"][PROGRAM]
    return runs["total_s"] / runs["runs"] * 1e3
