#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a toy size: every phase of a run, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload <cell> \
        [--seconds 5] [--seed 7] [--trace 0|1] [--scale '{"hosts": 64}'] \
        [--candidate <cell kept under candidates/>]

Skips the harness's look for a chip (``run.py`` itself never does: without a
TPU it prints no result and exits 3). The line it prints says
``"rehearsal_on": "cpu"`` among its checks; no number of it is a device
number. ``--scale`` overrides keys of the configuration's ``data``.
"""

import argparse
import json
import sys

import run

TOY = {"promperf_counters": {"apps": 3, "jobs": 4, "instances": 16,
                             "live_samples": 1500},
       "tsbs_cpu": {"hosts": 64}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", default=None)
    ap.add_argument("--candidate", default=None,
                    help="merge candidates/<name>.json into BENCHMARK.json")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    spec = run.Spec(args.workload, args.candidate)
    scale = json.loads(args.scale) if args.scale \
        else TOY.get(spec.config["datagen"], {})
    code, result = run.run_cell(
        args.workload, args.seed, args.seconds, args.trace,
        look_for_chip=False, scale=scale, control=args.control,
        fault=args.fault, candidate=args.candidate)
    print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main())
