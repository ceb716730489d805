#!/usr/bin/env python3
"""Repeat a cell to measure how its end-to-end metrics spread.

    python3 bench/tools/spread.py --workload NAME --seeds 1,2,3,4,5,6 \\
        --sets 2 [--seconds S] [--trace-seeds 7,8,9] [--out FILE]

Runs ``bench/run.py`` once per seed in each set (every run a process of
its own, one after the other; this parent never touches JAX), then each
``--trace-seeds`` run with ``--trace 1``.  Prints, per set and metric, the
median and the spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.  Writes
every result line to ``--out``.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

RUN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "run.py")


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def run_once(workload, seed, seconds, trace):
    t = time.perf_counter()
    p = subprocess.run([sys.executable, RUN, "--workload", workload, "--seed",
                        str(seed), "--seconds", str(seconds), "--trace",
                        str(trace)], capture_output=True, text=True)
    wall = time.perf_counter() - t
    line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "{}"
    try:
        out = json.loads(line)
    except json.JSONDecodeError:
        out = {}
    out.update(seed=seed, trace=trace, rc=p.returncode, wall_s=wall)
    print(json.dumps(out), flush=True)
    if p.returncode or not out.get("correct"):
        print(p.stderr[-4000:], file=sys.stderr, flush=True)
    return out


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return statistics.median(values), (q3 - q1) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace-seeds", type=_seeds, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(os.path.dirname(os.path.dirname(RUN)),
                               "BENCHMARK.json")) as f:
            args.seconds = json.load(f)["run_seconds"]
    rows = []
    for k in range(args.sets):
        for seed in args.seeds:
            rows.append(dict(run_once(args.workload, seed, args.seconds, 0),
                             set=k))
    for seed in args.trace_seeds:
        rows.append(dict(run_once(args.workload, seed, args.seconds, 1),
                         set="trace"))
    for k in range(args.sets):
        runs = [r for r in rows if r["set"] == k and r.get("metrics")]
        for name in sorted({m for r in runs for m in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in runs]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"set {k} {name}: median {med!r} spread {sp!r} "
                      f"values {vals}", flush=True)
    bad = [r["seed"] for r in rows if not r.get("correct")]
    print(f"runs {len(rows)}, not correct: {bad}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
