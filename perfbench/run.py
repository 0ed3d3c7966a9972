#!/usr/bin/env python3
"""The benchmark's one command (the contract is in PERF.md and
perfbench/README.md):

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from ``BENCHMARK.json``:
the configuration's file, the traffic mix's file (whose ``kind`` names the
driver), and one reader a metric. The last line of standard output is one
JSON object; earlier lines are for people. It measures on a TPU or not at
all: ``--rehearse`` (tiny widths from the files' own ``rehearse`` blocks,
Pallas interpreted, on the CPU) exists for the tests and says ``cpu``.
"""
import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import harness, traffic  # noqa: E402
from perfbench.harness import say  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU, kernels interpreted: "
                         "proves the harness, never the chip")
    ap.add_argument("--sweep", default=None, metavar="R1,R2,...",
                    help="open-loop mixes: one set-up, a window at each "
                         "rate (req/s); prints a table and no result line")
    return ap.parse_args(argv)


def rehearse_on_cpu():
    """chip_smoke.py --rehearse's switch: the CPU backend, ``on_tpu()``
    primed to yes, and every pallas_call in the Mosaic interpreter."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from unittest import mock

    import jax
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops import pallas as pallas_pack
    pallas_pack.on_tpu.cache_clear()
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        pallas_pack.on_tpu()
    pltpu.set_tpu_interpret_mode(pltpu.InterpretParams())


def resolve(args):
    """Every name of the cell to its file, before anything heavy is
    imported; an unknown one is refused here."""
    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_config(manifest, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    if args.rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    section = "per_layer" if args.trace else "end_to_end"
    metrics = harness.cell_metrics(manifest, section, cell["name"])
    package = "layer_metrics" if args.trace else "end_to_end"
    readers = [(m, harness.load_module(package, m["name"])) for m in metrics]
    family = harness.load_module("families", config["family"])
    driver = harness.load_module("drivers", mix["kind"])
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    return cell, config, mix, family, driver, readers


def main(argv=None):
    args = parse(argv)
    try:
        cell, config, mix, family, driver, readers = resolve(args)
    except harness.UnknownName as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        import paddle_tpu  # noqa: F401
    except ModuleNotFoundError as e:
        print(f"perfbench: the program under test is not here: {e}",
              file=sys.stderr)
        return 3
    if args.rehearse:
        rehearse_on_cpu()
    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"perfbench: {cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace}; platform={dev.platform} kind={dev.device_kind} "
        f"devices={len(devices)} jax={jax.__version__}"
        + (" REHEARSAL on the CPU: no number below is a measurement"
           if args.rehearse else ""))
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"perfbench: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform!r} device(s). Nothing "
              f"was run.", file=sys.stderr)
        return 4
    peaks = None if args.rehearse else harness.load_peaks(dev.device_kind)

    if not args.rehearse:       # a rehearsal leaves no cache behind
        from paddle_tpu.device import enable_compile_cache
        say(f"compile cache: {enable_compile_cache()}")
    ctx = harness.Context(args, cell, config, mix, family, STARTED)
    ctx.phases.append(("import, backend", time.perf_counter() - STARTED, 0,
                       0.0))
    say(f"set-up: {'import, backend':<16} {ctx.phases[0][1]:8.2f}s")
    if args.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    if args.sweep:
        from perfbench import serving
        serving.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        return 0

    obs = driver.run(ctx)
    obs.update(setup_s=ctx.setup_s, chips=ctx.chips, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              # the runtime's peak leaves out the programs' temp buffers
              # (PR 23: peak 4.48 GiB beside a 4.27 GB decode temp), which
              # are on the chip while a program runs: both parts are given
              "memory_peak_bytes": obs["runtime_peak_bytes"]
              + obs["program_temp_bytes"],
              "runtime_peak_bytes": obs["runtime_peak_bytes"],
              "program_temp_bytes": obs["program_temp_bytes"]}
    result = {"correct": bool(obs["correct"])}
    if args.trace:
        from perfbench import trace_reduce
        path = trace_reduce.find_trace(ctx.trace_dir)
        t_red = time.perf_counter()
        obs["trace"] = trace_reduce.reduce_rows(
            trace_reduce.load_rows(path),
            idle_default=obs["idle_default"]) if path else None
        if obs["trace"] is None:
            say("trace: no device operation in the traced window")
            result["correct"] = result["correct"] and args.rehearse
        else:
            tr = obs["trace"]
            say(f"trace: {path} reduced in "
                f"{time.perf_counter() - t_red:.1f}s: {tr['chips']} chip(s), "
                f"window {tr['window_s']:.3f}s, busy {tr['busy_s']:.3f}s, "
                f"self time by class {tr['self_s']}")
            for name, st in sorted(tr["modules"].items(),
                                   key=lambda kv: -kv[1]["total_s"])[:6]:
                say(f"  program {name}: {st['runs']} runs, median "
                    f"{st['median_s'] * 1e3:.3f} ms, total "
                    f"{st['total_s']:.3f} s")
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
            for name, s in tr["device_ops"]:
                say(f"  device op {s:9.4f}s  {name}")
            for name, s in tr["idle_gaps"]:
                say(f"  idle gap  {s:9.4f}s  {name}")

    result["attempted"] = obs["attempted"]
    result["failed"] = obs["failed"]
    metrics = {}
    for entry, reader in readers:
        value = reader.read(obs)
        if value is None:
            say(f"  {entry['name']}: nothing to read, left out")
            continue
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        say(f"  {entry['name']} = {value} {entry['unit']}")
    result["metrics"] = metrics
    result["device"] = device
    result["compiles_in_window"] = obs["compiles_in_window"]
    say(f"compile cache: {ctx.watch.cache_hits} hits, "
        f"{ctx.watch.cache_misses} misses; {ctx.watch.count} executables "
        f"built or loaded in {ctx.watch.seconds:.1f}s; whole run "
        f"{time.perf_counter() - STARTED:.1f}s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
