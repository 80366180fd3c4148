#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a toy size: every phase of a run, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload <cell> \
        [--seconds 5] [--seed 7] [--trace 0|1] [--scale '{"hosts": 64}'] \
        [--candidate <cell kept under candidates/>] [--controls-too]

Skips the harness's look for a chip (``run.py`` itself never does: without a
TPU it prints no result and exits 3). The line it prints says
``"rehearsal_on": "cpu"`` among its checks; no number of it is a device
number. ``--scale`` overrides keys of the configuration's ``data``; without
it the toy size is the configuration file's own ``"toy"`` where it has one,
else ``TOY``'s entry for its datagen (``run.py`` and ``node.py`` never read
``"toy"``).
"""

import argparse
import json
import sys

import run

TOY = {"promperf_counters": {"apps": 3, "jobs": 4, "instances": 16,
                             "live_samples": 1500},
       "tsbs_cpu": {"hosts": 64},
       "shards128_counters": {"apps": 8, "jobs": 4, "instances": 8}}


def toy_scale(config):
    """The toy size of a configuration: its own ``"toy"``, else ``TOY``'s."""
    return config.get("toy", TOY.get(config["datagen"], {}))


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
    ap.add_argument("--controls-too", action="store_true",
                    help="also read every control over the same answers")
    args = ap.parse_args()
    spec = run.Spec(args.workload, args.candidate)
    scale = json.loads(args.scale) if args.scale \
        else toy_scale(spec.config)
    code, result = run.run_cell(
        args.workload, args.seed, args.seconds, args.trace,
        look_for_chip=False, scale=scale, control=args.control,
        fault=args.fault, candidate=args.candidate,
        controls_too=args.controls_too)
    print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main())
