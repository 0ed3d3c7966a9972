"""Scheduler: the mean time from a streaming request's first token being drawn
(where the server's ``serving_ttft_seconds`` stops) to the return of its first
``on_token`` callback (where the client's clock stops): the decode dispatch and
read-back of the same turn lie between. ``serving_first_token_delivery_seconds``
of the server's telemetry registry, sum over count, the window's difference."""
from perfbench.layer_metrics import decode_dispatch_ms


def read(obs):
    return decode_dispatch_ms.window_mean_ms(
        obs, "serving_first_token_delivery_seconds")
