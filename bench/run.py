#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last stdout line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` at the root of the checkout.  The run sets up the
scheduler and service, warms up, drives rounds back to back for
``--seconds`` of wall time, checks the sampled rounds against the plain
reference, and prints one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` with ``--trace 1``) and,
last, ``checks``: each number compared with its limit, which also ends
standard error.  With ``--trace 1`` the metrics are the cell's per-layer
metrics, read from a profiler trace of the window; otherwise its end-to-end
metrics, with tracing off.  Where JAX finds no TPU, or fewer chips than the
cell asks for, it exits 3 and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    try:
        cell = harness.resolve_cell(args.workload)
        result = harness.measure(cell, args.seed, args.seconds,
                                 bool(args.trace), t_proc0=T_PROC0)
    except harness.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
