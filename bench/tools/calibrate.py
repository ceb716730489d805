#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tools/calibrate.py --workload NAME --seeds 1,2,3 \\
        [--control 4,5,6] [--fault half_batch:7,8,9] [--out FILE]

In one process: the program's first rounds against the reference on each
of ``--seeds`` (the lower readings), the control (the reference with its
weights stored and its matmul operands rounded to float8_e4m3fn, the
precision below the configuration's bfloat16) against the reference on
each ``--control`` seed, and each ``--fault`` (see ``harness.faults``)
planted in the program on its seeds.  Prints one JSON line per reading and
a summary, and writes them to ``--out``; each reading keeps the per-leaf
norms it was taken from (``leaves``), so that another statistic of them
can be read without another run.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import device, faults, main, spec  # noqa: E402

CONTROL = "float8_e4m3fn"


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


#: per-leaf norms a reading is taken from
LEAF_NUMBERS = ("dtheta1", "dTheta3", "lam3")


def _leaves(got, ref):
    return {k: {"got": got[k], "ref": ref[k]} for k in LEAF_NUMBERS
            if k in got and k in ref}


def main_(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control", type=_seeds, default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = device.chips(cell.chips)
    main.enable_compile_cache()
    rows = []

    def emit(kind, seed, read, extra=None):
        row = {"kind": kind, "seed": seed, **read, **(extra or {})}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def program(kind, seeds, wrap=None):
        if not seeds:
            return
        t = time.perf_counter()
        system, step = main.prepare(cell, devices, wrap)
        print(f"{kind}: compiled in {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
        for seed in seeds:
            state, _, _, prog = main.first_rounds(cell, system, step, seed)
            del state
            gc.collect()
            ref, read = main.reference_readings(cell, prog, seed, devices)
            emit(kind, seed, read,
                 {"prog_losses": prog["losses"], "ref_losses": ref["losses"],
                  "prog_inv_alpha": prog["inv_alpha"],
                  "ref_inv_alpha": ref["inv_alpha"],
                  "leaves": _leaves(prog, ref)})
        del system, step
        gc.collect()

    program("program", args.seeds)
    for seed in args.control:
        ctl = spec.load_reference(cell.traffic).run(
            cell.config, cell.traffic, seed, main.CHECK_ROUNDS,
            store=CONTROL, operand_dtype=CONTROL, devices=devices,
            keep_theta1="noise1" in cell.limits)
        ref, read = main.reference_readings(cell, ctl, seed, devices)
        emit("control", seed, read, {"ctl_losses": ctl["losses"],
                                     "leaves": _leaves(ctl, ref)})
    planted = {**faults.FAULTS, **faults.MESH_FAULTS}
    for f in args.fault:
        name, seeds = f.split(":")
        program(name, _seeds(seeds), planted[name])

    summary = {}
    for r in rows:
        s = summary.setdefault(r["kind"], {k: [] for k in cell.limits})
        for k in cell.limits:
            s[k].append(r[k])
    for kind, s in summary.items():
        print(f"{kind}: " + ", ".join(
            f"{k} max {max(v):.3e} min {min(v):.3e}" for k, v in s.items()),
            flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main_())
