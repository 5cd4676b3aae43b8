"""The spread of a cell's rate, without the check: each seed in a process of
its own, set-up and window exactly as ``benchmark.run`` makes them, then the
fit is ended and the process exits before the reference runs (the reference
follows the window and moves no number of it). For telling the seed's share
of a spread from the run's; no benchmark run does this.

    python3 -m benchmark.tools.rates --workload nemotron3_nano_fit \
        --seeds 11,11,12,13 --seconds 20 --out chiprun_out/rates.jsonl

One JSON line a run (``seed``, ``setup_s``, the rate, ``units``,
``window_s``, ``unit_seconds``, ``window_compiles``), then the median and
the spread of the rate: the distance between the first and third quartile
(``statistics.quantiles(n=4)``) over the median, of all runs and with the
run farthest from the median left out.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from benchmark import run as bench_run


def spread(values: list) -> float:
    """Quartile distance over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spreads(values: list) -> dict:
    med = statistics.median(values)
    out = {"median": med, "spread": spread(values)}
    if len(values) > 3:
        far = max(range(len(values)), key=lambda i: abs(values[i] - med))
        out["spread_without_farthest"] = spread(
            [v for i, v in enumerate(values) if i != far])
    return out


def one(workload: str, seed: int, seconds: float, rehearsal: bool) -> dict:
    """Set-up and window of one run, as ``benchmark.run.main`` makes them."""
    bench, cell, config, traffic = bench_run.load_cell(workload, rehearsal)
    if rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    bench_run.use_compile_cache()
    devs = bench_run._device(int(cell["chips"]), rehearsal)
    compiles = bench_run.CompileEvents()
    entry = bench_run._load_module("entries", traffic["entry"]).Entry(
        config, traffic, seed, int(cell["chips"]))
    entry.setup()
    setup_s = time.perf_counter() - bench_run._T0
    _, before = compiles.read()
    work, units = 0, 0
    t0 = time.perf_counter()
    while True:
        work += entry.unit()
        units += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    _, after = compiles.read()
    entry.check_inputs()         # ends the fit; what it returns is not judged
    return {"seed": seed, "setup_s": setup_s,
            traffic["rate_metric"]: work / window_s / int(cell["chips"]),
            "units": units, "window_s": window_s,
            "unit_seconds": list(getattr(entry, "unit_seconds", [])),
            "window_compiles": after - before,
            "device_kind": devs[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", help="comma-separated; a seed may repeat")
    ap.add_argument("--one", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    if args.one is not None:
        print(json.dumps(one(args.workload, args.one, args.seconds,
                             args.rehearsal)), flush=True)
        return 0

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cmd = [sys.executable, "-m", "benchmark.tools.rates", "--workload",
               args.workload, "--one", str(seed), "--seconds",
               str(args.seconds)] + (["--rehearsal"] if args.rehearsal else [])
        done = subprocess.run(cmd, cwd=bench_run.ROOT, stdout=subprocess.PIPE,
                              text=True)
        if done.returncode != 0:
            print(f"seed {seed}: exit {done.returncode}", file=sys.stderr)
            return done.returncode
        row = json.loads(done.stdout.strip().splitlines()[-1])
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    if len(rows) > 2:
        rate = next(k for k in rows[0] if k.startswith("train_")
                    or k.endswith("_per_s_chip"))
        summary = {"metric": rate, **spreads([r[rate] for r in rows]),
                   "setup_s": spreads([r["setup_s"] for r in rows])}
        print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
