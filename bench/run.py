#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from the seed, warms the cell's own shapes
(JAX's persistent compile cache lives in the checkout's ``.jax_cache``,
or where ``JAX_COMPILATION_CACHE_DIR`` says), measures for ``--seconds``
and checks every answer of the window against the plain reference. The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each number compared beside its limit, which also close
standard error. Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_devices(cell.entry["chips"])
    except harness.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    import jax
    from repro.utils import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = lambda s: print(s, file=sys.stderr, flush=True)   # noqa: E731
    log(f"[device] kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              devices=devices, log=log)
    harness.print_checks(result["checks"])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
