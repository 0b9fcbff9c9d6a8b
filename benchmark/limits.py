#!/usr/bin/env python3
"""Readings the limits in ``benchmark/limits/*.json`` are set from.

    python3 benchmark/limits.py --workload <name> --seeds 11,12,13 \\
        --control-seeds 3 --seconds 15 [--out chiprun_out/limits.jsonl]

Drives the cell as ``run.py`` does (same programs, same sizes, a shorter
window) once per seed in ONE process, and prints for each seed every
number compared: the program's (the lower reading is the largest of them
over a dozen seeds), and for the first ``--control-seeds`` seeds the 8-bit
control's and the planted faults' (the upper reading is the smallest).
The benchmark's own runs never run it. TPU only, like ``run.py``.
"""

import time

CLOCK0 = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--out", default=None)
    p.add_argument("--dry-run-cpu", action="store_true")
    args = p.parse_args(argv)

    from benchmark import harness
    cell = harness.Cell(harness.load_benchmark(), args.workload,
                        dry_run=args.dry_run_cpu)
    devices = harness.claim_devices(cell, args.dry_run_cpu)
    if devices is None:
        return 3
    runner = cell.runner()
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds)
        t = time.perf_counter()
        rec = runner.run(cell, run_args, devices, t, None,
                         control=i < args.control_seeds)
        line = {"workload": cell.name, "seed": seed,
                "device": devices[0].device_kind,
                "correct": all(c.ok for c in rec["checks"]),
                "readings": rec["readings"]}
        if not args.dry_run_cpu:    # a CPU run prints no device number
            line.update(end_to_end=rec["end_to_end"],
                        setup_s=rec["setup_s"],
                        seconds_total=time.perf_counter() - t)
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
        # the record holds the run's handles and arrays: drop it before
        # the next seed builds its own program beside it
        del rec, line, text
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
