#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``; every number compared beside its limit comes last
under ``checks`` and as the last lines of standard error.

It measures on a TPU only: with no accelerator, or fewer chips than the
cell asks for, it exits 3 and prints no result. ``--dry-run-cpu`` rehearses
the control flow at the tiny sizes the files give under ``dry_run``, says
``platform: cpu`` and never prints a result line.
"""

import time

CLOCK0 = time.perf_counter()        # process start, as near as Python gets

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--dry-run-cpu", action="store_true")
    args = p.parse_args(argv)

    # the program is imported before anything is printed: in a directory
    # that holds only the benchmark this fails, and there is no result
    import deeplearning4j_tpu  # noqa: F401
    from benchmark import compare, harness
    from benchmark.peaks import peaks_for

    bench = harness.load_benchmark()
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = harness.Cell(bench, args.workload, dry_run=args.dry_run_cpu)
    devices = harness.claim_devices(cell, args.dry_run_cpu)
    if devices is None:
        return 3
    peaks = None if args.dry_run_cpu else peaks_for(devices[0].device_kind)

    runner = cell.runner()
    tracer = harness.Tracer(args.seconds) if args.trace else None
    record = runner.run(cell, args, devices, CLOCK0, tracer)
    trace = tracer.trace if tracer is not None else None
    if tracer is not None:
        record["trace_interval"] = (tracer.t_start, tracer.t_stop)

    if args.dry_run_cpu:
        compare.print_checks(record["checks"], sys.stderr)
        print(json.dumps({
            "platform": "cpu", "dry_run": True, "workload": cell.name,
            "correct": all(c.ok for c in record["checks"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "note": "control flow only; no device metric is printed"}))
        return 0

    out = harness.result(cell, record, devices, trace, peaks)
    print(harness.memory_line(devices), file=sys.stderr)
    for k, v in record.get("notes", {}).items():
        print(f"note {k}: {v}", file=sys.stderr)
    compare.print_checks(record["checks"], sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
