"""Scheduler: of the time the serve loop gives the chip for a decode tick, the
share in which the chip ran it: 100 x the median DEVICE duration of
``jit_decode_tick`` in the traced slice, over the window's mean of
``decode_dispatch`` + ``decode_wait`` a tick (``serving_tick_phase_seconds``:
the host enqueueing the program, the device's run and the read-back of its
tokens). The rest is the host's enqueue, the runtime's and the transfer's
latency: what ROADMAP A2 would shorten. Not a roofline and not an MFU: it
compares two clocks over two spans of time (a median of the slice, a mean of
the window), so where they disagree by so much that the share passes 100 the
reader gives nothing rather than a number that cannot be."""
from perfbench.layer_metrics import decode_tick_ms, tick_host_share

DISPATCH, WAIT = ("decode_dispatch",), ("decode_wait",)


def read(obs):
    on_chip_ms = decode_tick_ms.read(obs)
    sums = tick_host_share.window_sums(obs, tick_host_share.PHASES)
    if on_chip_ms is None or not sums or DISPATCH not in sums \
            or WAIT not in sums:
        return None
    ticks = sums[WAIT][1]
    given = sums[DISPATCH][0] + sums[WAIT][0]
    if not ticks or given <= 0:
        return None
    share = 100.0 * (on_chip_ms / 1e3) / (given / ticks)
    return share if share <= 100.0 else None
