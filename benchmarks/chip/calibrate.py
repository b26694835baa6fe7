#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,... [--control-seeds 1,2,3] [--fault <name>] \
        --seconds <s>

Runs the cell's timed path once per seed in one process (the same set-up,
window, sample and comparison as a benchmark run, with the cell's
limits) and prints one JSON line per seed: the widest gap of the served
tokens below the float32 reference's best and the verdict, and the
cell's end-to-end metrics.  On the control seeds the reference in float8
(the control) is also put in the program's place and judged by the same
rule.  ``--fault`` plants one of ``faults.py``'s faults in the node on
every seed.  The benchmark's own runs run neither.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import faults
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}

    bench = run.cells.load_benchmark()
    cell = run.cells.workload(bench, args.workload)
    spec = run.cells.config(bench, cell["config"])
    mix = run.cells.traffic(cell["traffic"])
    metrics = run.cells.reported(bench, cell["name"], "end_to_end")
    import verdict
    limits = verdict.load_limits(cell["name"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    import arith
    pk = arith.peaks(devices[0].device_kind)
    run.use_compile_cache(jax)
    compiles = run.CompileCount(jax)
    plant = faults.FAULTS[args.fault] if args.fault else None
    for seed in seeds:
        t = time.perf_counter()
        res = run.measure(spec, mix, seed, args.seconds, 0, devices[0],
                          limits, metrics, pk, compiles=compiles, t_start=t,
                          control=seed in ctl, plant=plant)
        c = res["checks"]
        line = {"workload": cell["name"], "seed": seed, "fault": args.fault,
                "correct": res["correct"],
                "gap": c.get("logit_gap_max", {}).get("value"),
                "limit": limits["logit_gap_max"]["limit"],
                "tokens": c.get("tokens_checked", {}).get("value"),
                "requests": c["requests_checked"]["value"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "seconds": time.perf_counter() - t}
        if "control" in res:
            line["control_correct"] = res["control"]["correct"]
            line["control_gap"] = \
                res["control"]["checks"]["logit_gap_max"]["value"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
