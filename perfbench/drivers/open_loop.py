"""Requests arrive on a schedule whatever the server does (independent
users). After the window the harness drains for ``drain_s`` so that late
first tokens are seen; a first token still owed then counts as the worst,
and as failed."""
from perfbench import serving


def run(ctx):
    return serving.serve(ctx, drain_s=float(ctx.mix["drain_s"]))
