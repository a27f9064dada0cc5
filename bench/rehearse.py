#!/usr/bin/env python3
"""CPU rehearsals of a cell, through the harness's own functions.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell> --rounds N
    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <cell> --compile

``--rounds`` runs the cell's system for N rounds from its set-up and prints,
for each block of ``--block`` rounds, the mean live jobs, bids, windows,
awards and conflicts a round, the jobs completed, and the jobs arrived and
shed so far: the levelness check of a traffic mix.  Decisions do not depend
on the device, so the counts carry over to the chip; the times do not.
``--rate`` overrides a Poisson mix's arrival rate for a sweep.

``--compile`` compiles the ``jasda_score`` kernel for a described v5e (no
chip) at the largest score bucket that ``--rounds`` saw (``--bucket``
gives it directly), with the operand shapes the scheduler packs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def levelness(cell, seed: int, rounds: int, block: int) -> dict:
    import harness

    rows = []
    state = {"n": 0, "t": time.perf_counter()}

    def on_pull(now):
        if state["n"] >= rounds:
            raise harness.StopWindow
        state["n"] += 1

    sched, svc, stream = harness.build_system(cell, harness.seeds(seed),
                                              on_pull)
    last = {"t": time.perf_counter(), "completed": 0}
    orig = sched._settle_round

    def settle(prep):
        rr = orig(prep)
        now = time.perf_counter()
        live = sum(1 for a in sched.agents.values() if not a.finished)
        rows.append({"t": prep.now, "wall": now - last["t"], "live": live,
                     "bids": len(prep.fit), "windows": len(prep.windows),
                     "awards": len(rr.selected) if rr else 0,
                     "conflicts": rr.n_conflicts if rr else 0,
                     "completed": svc.metrics.n_completed - last["completed"],
                     "arrived": svc.metrics.n_arrived,
                     "shed": svc.metrics.n_shed})
        last["t"], last["completed"] = now, svc.metrics.n_completed
        return rr

    sched._settle_round = settle
    try:
        svc.run(t_end=harness.HORIZON)
    except harness.StopWindow:
        pass
    blocks = []
    for b in range(0, len(rows), block):
        part = rows[b:b + block]
        mean = {k: sum(r[k] for r in part) / len(part)
                for k in ("wall", "live", "bids", "windows", "awards",
                          "conflicts", "completed")}
        mean.update(first=part[0]["t"], max_bids=max(r["bids"] for r in part),
                    min_live=min(r["live"] for r in part),
                    max_live=max(r["live"] for r in part),
                    device_rounds=sum(r["bids"] >= 256 for r in part),
                    min_bids=min(r["bids"] for r in part),
                    arrived=part[-1]["arrived"], shed=part[-1]["shed"])
        blocks.append(mean)
    return {"blocks": blocks,
            "shed_share": svc.metrics.n_shed / max(1, svc.metrics.n_arrived),
            "max_bids": max((r["bids"] for r in rows), default=0),
            "device_share": (sum(r["bids"] >= 256 for r in rows)
                             / max(1, len(rows))),
            "wall_per_round": sum(r["wall"] for r in rows) / max(1, len(rows))}


def compile_bucket(m: int) -> dict:
    """Compile the score kernel at bucket m for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.jasda_score.kernel import score_variants_pallas

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    args = (sds((m, 1)), sds((m, 4)), sds((1,)), sds((4,)), sds((m, 1)),
            sds((m, 1)))
    kw = dict(lam=sds((m,)), capacity=sds((m,)), theta=sds((m,)))
    t0 = time.perf_counter()
    compiled = score_variants_pallas.lower(*args, block_m=256,
                                           **kw).compile()
    mem = compiled.memory_analysis()
    return {"bucket": m, "compile_s": time.perf_counter() - t0,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--block", type=int, default=10)
    ap.add_argument("--rate", type=float)
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--bucket", type=int)
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve_cell(args.workload)
    if args.rate is not None:
        cell.mix["arrivals"]["rate"] = args.rate
    out = {"workload": args.workload, "arrivals": cell.mix["arrivals"]}
    bucket = args.bucket
    if args.rounds:
        lv = levelness(cell, args.seed, args.rounds, args.block)
        for b in lv["blocks"]:
            print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                              for k, v in b.items()}), flush=True)
        out.update(max_bids=lv["max_bids"], device_share=lv["device_share"],
                   shed_share=lv["shed_share"],
                   wall_per_round=lv["wall_per_round"])
        if bucket is None:
            bucket = 256
            while bucket < lv["max_bids"]:
                bucket *= 2
    if args.compile:
        out["compile"] = compile_bucket(bucket or 256)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
