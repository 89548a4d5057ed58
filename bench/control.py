#!/usr/bin/env python3
"""Run a cell with the control in the engine's place, on several seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

The control is the plain reference computed from bfloat16 operands
(``reference.BF16Engine``), served behind the engine's interface at the
cell's own size and load. Each seed prints the numbers compared with
their limits and whether the run came out correct; the control has to
come out not correct on every seed. The benchmark's own runs never run
it.
"""
import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_engine(config, lane, capacity, disk_path, seed, base, attrs):
    from bench.reference import BF16Engine
    return BF16Engine(base, capacity, config["k"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_devices(cell.entry["chips"])
    except harness.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    from repro.utils import use_compile_cache
    use_compile_cache()
    log = lambda s: print(s, file=sys.stderr, flush=True)   # noqa: E731
    wrong = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), devices=devices,
                               make_engine=control_engine, log=log)
        wrong += not res["correct"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    print(f"control came out not correct on {wrong} of "
          f"{len(args.seeds.split(','))} seeds", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
