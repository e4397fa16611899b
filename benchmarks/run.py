"""Benchmark harness entry point: one benchmark per paper table/figure.

  singular_bounds -- Sec. 5 bound tightness (Prop 5.1 / 5.2)
  comm_cost       -- Figs. 2-5 (high/low D2S regimes)
  dropout_sweep   -- d2s/d2d-per-accuracy over dropout rate x topology
                     family x straggler model (iid vs bursty Markov)
  adaptive_sweep  -- closed-loop threshold controller vs the static
                     plan: D2S spend to a target accuracy
  staleness_sweep -- semi-async StreamEngine: buffer size x upload
                     latency distribution (late/lost/staleness totals)
  ingest_sweep    -- wall-clock IngestEngine: overlapped vs serial
                     dispatch rounds/sec (replay-verified recordings)
  convergence     -- Theorem 4.5 O(1/t) envelope
  mixing_kernel   -- Pallas D2D-mixing kernel vs oracle
  roofline_table  -- §Roofline terms from dry-run artifacts; not in the
                     default list (``--only roofline_table``), and it
                     fails when the artifacts are absent

``python -m benchmarks.run [--only NAME] [--fast] [--json-out PATH]
[--check-baseline PATH] [--plan PATH]``

``--plan PATH`` pins the trajectory: the convergence benchmark executes
the serialized ``RoundPlan`` (``repro.fl.plan``) instead of sampling a
fresh one, so benchmark trajectories are reproducible artifacts (write
one with ``python -m repro.launch.train --plan-out``).

Results are written to ``BENCH_mixing.json`` by default so the perf
trajectory (fused vs two-pass mixing wall time + bytes-moved model +
measured per-dtype grouped payload bytes) is tracked across PRs; pass
``--json-out ''`` to skip the artifact.

``--check-baseline PATH`` compares the fresh mixing_kernel payload-byte
fields against a committed baseline (the repo's BENCH_mixing.json) and
exits non-zero if any modeled or measured payload bytes regressed --
byte fields compare as exact integer counts (a non-integral value is
itself an error); wall times are machine-dependent and deliberately NOT
compared.  On success it prints a one-line PASS summary with the row
and field counts actually checked.  CI runs this on every push.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.launch.cache import enable_compile_cache

from . import (comm_cost, convergence, dropout_sweep, ingest_throughput,
               mixing_kernel, roofline_table, singular_bounds,
               topology_ablation)

DEFAULT_BENCHES = ("singular_bounds", "topology_ablation", "comm_cost",
                   "dropout_sweep", "adaptive_sweep", "staleness_sweep",
                   "ingest_sweep", "convergence", "mixing_kernel")
BENCHES = DEFAULT_BENCHES + ("roofline_table",)

# payload-byte fields pinned by --check-baseline: deterministic models /
# measurements (never wall times), so any increase is a real regression
_BYTE_FIELDS = ("bytes_two_pass", "bytes_fused", "bytes_agg_only",
                "bytes_grouped", "bytes_quantized",
                "bytes_psum_per_worker",
                "bytes_reduce_scatter_per_worker")


def _row_key(row):
    """Stable identity of a mixing_kernel result row across runs."""
    if row.get("kind") == "grouped_payload":
        return ("grouped_payload", row.get("layout"), row.get("n"))
    if row.get("kind") == "quant_payload":
        return ("quant_payload", row.get("layout"), row.get("n"),
                row.get("storage"))
    if row.get("kind") == "plan_overhead":
        return ("plan_overhead", row.get("n"), row.get("rounds"))
    if row.get("kind") == "sparse_vs_dense":
        return ("sparse_vs_dense", row.get("n"), row.get("p"))
    return ("kernel", row.get("n"), row.get("p"), row.get("dtype"))


def _as_byte_count(value, key, field, problems):
    """Byte fields are exact counts: coerce to int, flagging anything
    non-integral (a fractional 'byte count' means the model computed a
    rate, not bytes -- comparing those as floats silently passes on
    representation jitter).  Returns None after flagging."""
    f = float(value)
    if not f.is_integer():
        problems.append(
            f"{key}: {field} is non-integral ({value!r}) -- byte fields "
            "must be exact integer counts")
        return None
    return int(f)


def check_baseline(new_rows, baseline_path, stats=None) -> list:
    """Compare payload-byte fields of fresh mixing_kernel rows against the
    committed baseline; returns a list of human-readable regressions.

    Every baseline row and every baseline byte field must find a
    counterpart in the fresh results -- a pinned row/field silently
    disappearing from the benchmark would otherwise turn the gate green
    while checking nothing.  Byte fields compare as exact integers (a
    non-integral value is itself an error).  Pass a dict as ``stats`` to
    receive ``rows_checked`` / ``fields_compared`` counts back."""
    with open(baseline_path) as f:
        base_rows = json.load(f).get("mixing_kernel", [])
    base = {_row_key(r): r for r in base_rows}
    new = {_row_key(r): r for r in new_rows}
    problems = []
    rows_checked = fields_compared = 0
    for key, old in base.items():
        row = new.get(key)
        if row is None:
            problems.append(
                f"{key}: baseline row has no counterpart in the fresh "
                "results -- pinned benchmark entry dropped or renamed")
            continue
        rows_checked += 1
        for field in _BYTE_FIELDS:
            if field not in old:
                continue
            if field not in row:
                problems.append(
                    f"{key}: pinned field {field} missing from the fresh "
                    "results")
                continue
            new_v = _as_byte_count(row[field], key, field, problems)
            old_v = _as_byte_count(old[field], key, field, problems)
            if new_v is None or old_v is None:
                continue
            fields_compared += 1
            if new_v > old_v:
                problems.append(
                    f"{key}: {field} regressed "
                    f"{old_v:d} -> {new_v:d} bytes")
    if not base:
        problems.append(
            f"no mixing_kernel rows in {baseline_path} -- baseline stale "
            "or malformed")
    if stats is not None:
        stats["rows_checked"] = rows_checked
        stats["fields_compared"] = fields_compared
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=BENCHES)
    ap.add_argument("--fast", action="store_true",
                    help="reduced trial counts / rounds")
    ap.add_argument("--json-out", default=None,
                    help="benchmark artifact path; defaults to "
                         "BENCH_mixing.json whenever the mixing_kernel "
                         "bench runs (tracking the perf trajectory across "
                         "PRs) and to no artifact otherwise; pass '' to "
                         "disable")
    ap.add_argument("--check-baseline", default=None, metavar="PATH",
                    help="compare fresh mixing_kernel payload bytes "
                         "against this committed baseline JSON and exit "
                         "non-zero on regression (CI gate)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="serialized RoundPlan JSON: the convergence "
                         "benchmark replays this pinned trajectory "
                         "instead of sampling a fresh one")
    args = ap.parse_args(argv)
    enable_compile_cache()

    results = {}
    selected = [args.only] if args.only else list(DEFAULT_BENCHES)
    if args.json_out is None:
        # only default-write the tracked artifact when its contents
        # actually include the mixing bench (don't clobber it with a
        # different subset's results)
        args.json_out = ("BENCH_mixing.json"
                         if "mixing_kernel" in selected else "")

    for name in selected:
        print(f"\n=== {name} " + "=" * (60 - len(name)), flush=True)
        t0 = time.time()
        if name == "singular_bounds":
            results[name] = singular_bounds.run(
                trials=50 if args.fast else 200)
        elif name == "topology_ablation":
            results[name] = topology_ablation.run(
                trials=10 if args.fast else 50)
        elif name == "comm_cost":
            rounds = 6 if args.fast else 15
            results[name] = (comm_cost.run("high", rounds=rounds)
                             + comm_cost.run("low", rounds=rounds))
        elif name == "dropout_sweep":
            results[name] = dropout_sweep.run(
                rates=(0.0, 0.2) if args.fast else (0.0, 0.1, 0.3),
                rounds=3 if args.fast else 6)
            results[name] += dropout_sweep.run_quant(
                rates=(0.0,) if args.fast else (0.0, 0.2),
                rounds=3 if args.fast else 6)
        elif name == "adaptive_sweep":
            results[name] = dropout_sweep.run_adaptive(
                rounds=3 if args.fast else 6)
        elif name == "staleness_sweep":
            results[name] = dropout_sweep.run_staleness(
                buffers=(None, 6) if args.fast else (None, 12, 6),
                rounds=3 if args.fast else 6)
        elif name == "ingest_sweep":
            results[name] = ingest_throughput.run(
                rounds=4 if args.fast else 8,
                d=384 if args.fast else 768)
        elif name == "convergence":
            results[name] = convergence.run(rounds=10 if args.fast else 40,
                                            plan_path=args.plan)
        elif name == "mixing_kernel":
            results[name] = mixing_kernel.run()
        elif name == "roofline_table":
            results[name] = [dict(arch=r["arch"], shape=r["shape"],
                                  dominant=r["dominant"])
                             for r in roofline_table.run()]
        print(f"--- {name}: {time.time() - t0:.1f}s", flush=True)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1, default=str)

    if args.check_baseline is not None:
        if "mixing_kernel" not in results:
            print("--check-baseline: mixing_kernel did not run")
            return 2
        stats = {}
        problems = check_baseline(results["mixing_kernel"],
                                  args.check_baseline, stats=stats)
        if problems:
            print("\npayload-bytes regressions vs "
                  f"{args.check_baseline}:")
            for p in problems:
                print(f"  {p}")
            return 2
        print(f"\nPASS: payload bytes OK vs baseline "
              f"{args.check_baseline} "
              f"({stats['rows_checked']} rows checked, "
              f"{stats['fields_compared']} byte fields compared)")

    print("\nall benchmarks complete")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
