#!/usr/bin/env python3
"""One traced run of a cell, keeping its trace, to read it by hand.

    python3 bench/tools/trace_dump.py --workload NAME --seed N --seconds S \\
        --out DIR

Runs the cell as ``bench/run.py --trace 1`` does, with every reader under
``bench/metrics`` (not only the cell's) reading its trace, copies the
``.xplane.pb`` into ``DIR``, and prints every plane and line of the trace
with its event count, and the 40 device operations that took most time,
each with the statistics the profiler keeps for one of its events.
"""
import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import device, main, spec  # noqa: E402
from harness import trace as trmod  # noqa: E402


def main_(argv=None):
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = device.chips(cell.chips)
    main.enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    kept = {}
    load = trmod.load

    def keep(path):
        kept["path"] = shutil.copy(path, os.path.join(args.out,
                                                      "trace.xplane.pb"))
        return load(path)

    trmod.load = keep
    # every reader under bench/metrics, the cell's and the others, reads
    # this trace
    known = {m["name"] for m in cell.per_layer}
    extra = [{"name": os.path.basename(f)[:-3], "unit": "%"}
             for f in sorted(glob.glob(os.path.join(spec.BENCH_DIR, "metrics",
                                                    "*.py")))
             if os.path.basename(f)[:-3] not in known]
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + extra)
    out = main.run_cell(cell, args.seed, args.seconds, True, devices, t_start)
    print(json.dumps(out), flush=True)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(kept["path"])
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name!r}: {lines}")
    tr = load(kept["path"])
    stats = {}
    for plane in pd.planes:
        for ln in plane.lines:
            if plane.name.startswith("/device:") and ln.name == "XLA Ops":
                for e in ln.events:
                    stats.setdefault(e.name, [(k, str(v)[:240])
                                              for k, v in e.stats])
    for name, s in trmod.top_ops(tr, 40):
        print(f"op {s:.6f} s {name} stats {stats.get(name)}")
    for d, ops in tr.devices.items():
        print(f"device {d}: {len(ops)} ops, first {ops[:3]}")
    print(f"host spans {len(tr.host)}: first {tr.host[:6]}")
    return 0


if __name__ == "__main__":
    sys.exit(main_())
