#!/usr/bin/env python3
"""Readings that a training cell's limits are set from (on the chip).

    python bench/calibrate.py --workload <name> --seeds 1,2,3
        [--seconds S] [--out F]

For each seed, in one process: set-up and a window of ``--seconds`` (at
least one segment) as a benchmark run makes them, then the plain
reference follows the rounds a run compares, in float32 at "highest";
the program's numbers against it are the lower readings.  The reference
put in the program's place one precision lower (the control,
``bfloat16``) and with each of the reference's faults gives the upper
readings.  One JSON line per seed and variant:
``{"seed", "variant", <number>: value, ...}``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL = "bfloat16"


def readings(cell, seed, devices, seconds=0.0, faults=None):
    """Yield ``(variant, numbers)``: the program, the control and each of
    the reference's faults, each against the float32 reference."""
    from bench.harness.compare import comm_mismatches
    from bench.harness.spans import Spans

    reference = cell.reference()
    system = cell.system().System(cell, seed, Spans(), devices)
    system.setup()
    system.window(seconds)
    system.release()
    numbers = system.numbers(reference)
    numbers["comm_mismatch"] = float(comm_mismatches(
        system.plans, system.histories, reference.comm))
    yield "program", numbers
    variants = [("control", CONTROL, None)] + [
        (f, "float32", f)
        for f in (reference.FAULTS if faults is None else faults)]
    for name, dtype, fault in variants:
        yield name, system.numbers(reference, dtype=dtype, fault=fault)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import cells
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.resolve(args.workload)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            for variant, numbers in readings(cell, seed, jax.devices(),
                                             args.seconds):
                line = json.dumps({"seed": seed, "variant": variant,
                                   **numbers})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
            print(f"seed {seed}: {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr, flush=True)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
