#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Set-up builds the cell's system from its configuration and traffic files,
drives its first rounds (which the reference follows) and compiles every
program the window uses; then the window runs for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  After the window the program's state is freed and the plain
reference checks what the program produced; each number compared is
printed beside its limit.

The last line of standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  The persistent compilation cache lives
in ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from bench.harness import cells
        cell = cells.resolve(args.workload)
    except (OSError, KeyError, ImportError) as e:
        return _fail(f"cannot resolve workload {args.workload!r}: {e}")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: jax found {dev.platform!r} devices")
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, found "
                     f"{len(devices)}")

    from bench.harness.peaks import peaks_for
    from bench.harness.runner import print_result, run_cell
    from repro.kernels.dispatch import default_interpret
    from repro.launch.cache import enable_compile_cache

    if default_interpret():
        return _fail("Pallas kernels would run in the interpreter "
                     "(REPRO_PALLAS_INTERPRET); unset it")
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        return _fail(str(e))
    enable_compile_cache()
    # every program the window runs goes to the cache, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks, T_START)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
