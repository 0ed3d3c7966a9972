"""Tick programs: the median DEVICE duration, in the traced slice, of the
decode tick's program. The program is found by the name the server gives it
(``hoisted_jit`` names a program after its function: ``jit_decode_tick``); a
tree without that name has nothing to read."""
PROGRAM = "jit_decode_tick"


def read(obs):
    trace = obs.get("trace")
    if not trace or PROGRAM not in trace["modules"]:
        return None
    return trace["modules"][PROGRAM]["median_s"] * 1e3
