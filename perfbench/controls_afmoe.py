#!/usr/bin/env python3
"""Show that the ``afmoe`` cell's check catches the mechanism: run the cell
as ``perfbench/run.py`` does, and after its own check read a seeded sample of
the same completed requests against negative controls of the plain
reference, with the harness's own ``check_tokens`` and limits.

    python3 perfbench/controls_afmoe.py --workload trinity-mini-26b-a3b.mixed-steady \\
        --seed <n> --seconds <s> --trace <0|1> [--rehearse]

Each control prints one line, ``control <name>: <n> requests, worst gap
<std> std, argmax <share>: FAILS|PASSES``. The controls, in order:
``window_whole_context`` (a window layer attends the whole context),
``rope_on_full_layer`` (the full layers turn q and k too), ``no_output_gate``,
``experts_by_score_alone`` (the top 8 by the sigmoid score: the bias never
chooses), ``bias_weighs`` (the chosen experts weighed by ``s + expert_bias``;
a few percent on a row's weights, so it may pass), and ``leaves_4_bits``
(every floating leaf of two or more dimensions rounded to 4 significant bits,
the nearest precision below bfloat16). ``CONTROL_PICKS`` in the environment
caps the requests a control reads (all of the check's sample by default). The
result line is the run's own; the controls change nothing in it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perfbench import serving  # noqa: E402
from perfbench.harness import say  # noqa: E402

CONTROLS = (("window_whole_context", dict(window=False)),
            ("rope_on_full_layer", dict(rope_full=True)),
            ("no_output_gate", dict(gate=False)),
            ("experts_by_score_alone", dict(route="score")),
            ("bias_weighs", dict(route="bias_weighs")),
            ("leaves_4_bits", {}))

_check_sample = serving.check_sample


def round_4_bits(a):
    """``a`` with its mantissa rounded to 4 significant bits."""
    import jax.numpy as jnp
    m, e = jnp.frexp(a.astype(jnp.float32))
    return (jnp.round(m * 16.0) / 16.0 * jnp.exp2(e.astype(jnp.float32))
            ).astype(a.dtype)


def check_sample(ctx, model, schedule, requests):
    """The cell's own check, then each control over the same sample."""
    import jax

    from perfbench import reference_afmoe
    ok = _check_sample(ctx, model, schedule, requests)
    done = [i for i, r in enumerate(requests) if r["done_at"] is not None]
    if not done:
        return ok
    rng = np.random.default_rng([int(ctx.seed), 11])
    picks = rng.choice(done, min(serving.CHECKED_REQUESTS, len(done)),
                       replace=False)
    picks = picks[:int(os.environ.get("CONTROL_PICKS", len(picks)))]
    width = ctx.mix["server"]["max_cache_len"]
    sizes = ctx.family.sizes(ctx.config, ctx.rehearse)
    params = model.raw_params()
    for name, kw in CONTROLS:
        if name == "leaves_4_bits":
            q = jax.jit(round_4_bits, donate_argnums=0)
            params = {n: (q(a) if a.ndim >= 2 and
                          jax.numpy.issubdtype(a.dtype, jax.numpy.floating)
                          else a) for n, a in params.items()}

        def ref(ids, kw=kw):
            return reference_afmoe.row_logits(
                params, ids, width, sizes, head_rows=ctx.family.HEAD_ROWS,
                **kw)
        worst, agree, emitted = 0.0, 0, 0
        for i in picks:
            _, gap, same, _ = serving.check_tokens(
                f"request {i}", ref, schedule[i]["prompt"],
                requests[i]["tokens"])
            worst, agree = max(worst, gap), agree + same
            emitted += len(requests[i]["tokens"])
        share = agree / emitted
        fails = (worst > serving.LOGIT_MARGIN_STD
                 or share < serving.ARGMAX_AGREE_MIN)
        say(f"control {name}: {len(picks)} requests, worst gap {worst:.4f} "
            f"std, argmax {share:.4f}: {'FAILS' if fails else 'PASSES'}")
    return ok


serving.check_sample = check_sample

if __name__ == "__main__":
    from perfbench import run
    sys.exit(run.main(sys.argv[1:]))
