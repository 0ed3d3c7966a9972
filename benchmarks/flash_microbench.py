"""Microbench: Pallas flash attention (fwd+bwd) vs plain XLA attention on
the TPU chip. Emits a markdown table (stdout). Fails without a TPU.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa


def _fetch(out):
    # a host fetch of a value that depends on the result: the work is done
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def bench(fn, *args, iters=20):
    _fetch(fn(*args))   # compile
    _fetch(fn(*args))   # steady-state warmup
    t0 = time.perf_counter()
    for _ in range(iters - 1):
        fn(*args)
    _fetch(fn(*args))
    return (time.perf_counter() - t0) / iters


def main():
    from paddle_tpu.device import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_microbench measures a TPU; JAX found "
                         f"platform {dev.platform!r}. No measurement.")
    enable_compile_cache()
    print(f"device: {dev.device_kind}", file=sys.stderr)
    b, h, d = 4, 16, 128
    causal = True
    rows = []
    for s in (1024, 2048, 4096):
        rng = np.random.RandomState(0)
        mk = lambda: jax.device_put(jnp.asarray(
            rng.randn(b, h, s, d).astype(np.float32) * 0.3,
            dtype=jnp.bfloat16), dev)
        q, k, v = mk(), mk(), mk()
        sm = 1.0 / np.sqrt(d)

        def pallas_step(q, k, v):
            def loss(q, k, v):
                return fa._flash(q, k, v, sm, causal).astype(
                    jnp.float32).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        def xla_step(q, k, v):
            def loss(q, k, v):
                return fa._ref_attention(q, k, v, sm, causal).astype(
                    jnp.float32).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        def pallas_fwd(q, k, v):
            return fa._flash(q, k, v, sm, causal)

        def xla_fwd(q, k, v):
            return fa._ref_attention(q, k, v, sm, causal)

        t_pf = bench(jax.jit(pallas_fwd), q, k, v)
        t_xf = bench(jax.jit(xla_fwd), q, k, v)
        t_ps = bench(jax.jit(pallas_step), q, k, v)
        t_xs = bench(jax.jit(xla_step), q, k, v)

        # causal attention FLOPs: fwd 2 matmuls = 4*b*h*s^2*d * 0.5;
        # bwd 5 matmuls = 10*b*h*s^2*d * 0.5
        f_fwd = 2.0 * b * h * s * s * d
        f_tot = 7.0 * b * h * s * s * d
        rows.append((s,
                     t_pf * 1e3, f_fwd / t_pf / 1e12,
                     t_xf * 1e3, f_fwd / t_xf / 1e12,
                     t_ps * 1e3, f_tot / t_ps / 1e12,
                     t_xs * 1e3, f_tot / t_xs / 1e12))
        print(f"seq={s} done", file=sys.stderr)

    print(f"\nShapes b={b} h={h} d={d} bf16 causal; device {dev.device_kind}")
    print("| seq | pallas fwd ms (TF/s) | xla fwd ms (TF/s) | "
          "pallas fwd+bwd ms (TF/s) | xla fwd+bwd ms (TF/s) |")
    print("|---|---|---|---|---|")
    for s, pf, pft, xf, xft, ps, pst, xs, xst in rows:
        print(f"| {s} | {pf:.2f} ({pft:.1f}) | {xf:.2f} ({xft:.1f}) | "
              f"{ps:.2f} ({pst:.1f}) | {xs:.2f} ({xst:.1f}) |")


if __name__ == "__main__":
    main()
