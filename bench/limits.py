#!/usr/bin/env python3
"""Readings that set the output check's limits, for one cell, in one process.

    python3 bench/limits.py --workload <cell> --seeds 1 2 3 ... --seconds 8

For each seed: one run of the cell with a short window at the cell's own
load and sizes, its sampled rounds compared with the reference (the
program's readings), then the same rounds with the reference in bfloat16 in
the program's place (the control's readings).  Prints one JSON line per
seed and a summary: the largest program reading and the smallest control
reading of each number.  The benchmark's own runs do not run this.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu logs under /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (rehearsal only)")
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve_cell(args.workload)
    harness.device_info(cell.chips, require_tpu=not args.cpu)
    if not args.cpu:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    program, control = {}, {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = harness.drive(cell, seed, args.seconds, trace=False,
                            t_proc0=t0, require_tpu=not args.cpu)
        got = {k: v["value"] for k, v in harness.check_outputs(run).items()}
        ctl = {k: v["value"] for k, v in
               harness.check_outputs(run, control=True).items()}
        print(json.dumps({"seed": seed, "rounds": run.rounds,
                          "sampled_bids": [r["n_bids"] for r in run.sampled],
                          "program": got, "control": ctl}), flush=True)
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
        for k, v in ctl.items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "program_max": program, "control_min": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
