#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep up with arrivals.

    python3 bench/knee.py --workload deep1b.pq.open --seed <n> \
        --seconds 12 --rates 200,400,800,1200

Builds the cell's deployment once, warms it as a run does, then offers
each rate in turn for ``--seconds``, waiting for every answer before the
next. Per rate it prints the offered and completed rates, the backlog at
the close (requests sent and not yet answered), p50 and p99 from the due
time, and how late the generator ran. A rate keeps up when the window
completes at least 95% of what it offers: below the knee the shortfall
is only the requests in flight at the close; above it the backlog grows
all through the window. The cell's file takes 0.8 x the highest rate
that keeps up, written in by hand.
"""
import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.data import make_attributes, make_vectors
    from bench.window import latencies_ms, nearest_rank
    cell = harness.load_cell(args.workload)
    try:
        harness.require_devices(cell.entry["chips"])
    except harness.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    import jax
    from repro.utils import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = cell.config
    rates = [float(r) for r in args.rates.split(",")]
    mod = harness.traffic_module(cell.spec["traffic_kind"])
    params = dict(cell.spec["traffic_params"])
    trafs = [mod.Traffic(dict(params, rate_qps=r), args.seconds, args.seed,
                         cfg["max_batch"]) for r in rates]
    n_q = max(t.query_rows() for t in trafs)
    n0 = cfg["n"]
    x = make_vectors(args.seed, n0 + n_q, cfg["dim"], cfg["latent_dim"])
    attrs = make_attributes(args.seed, n0, cfg["attributes"])
    with tempfile.TemporaryDirectory(prefix="svf-knee-") as work:
        build = harness.engine_module(cell.spec["engine"]).build
        eng = build(cfg, cell.spec["lane"], n0, os.path.join(work, "index"),
                    args.seed, x[:n0], attrs)
        try:
            queries = x[n0:]
            trafs[0].warm(eng, queries, None)
            print(f"[set-up] s={time.perf_counter() - T_START:.3f}",
                  flush=True)
            for rate, t in zip(rates, trafs):
                t0 = time.perf_counter()
                reqs, _ = t.run(eng, queries, None, t0, args.seconds)
                t1 = t0 + args.seconds
                answered = [r for r in reqs if r.answered]
                backlog = sum(1 for r in reqs
                              if r.sent is not None and r.sent < t1
                              and (r.done is None or r.done > t1))
                lat = latencies_ms(reqs, t0, t1, time.perf_counter())
                late = [r.sent - r.due for r in reqs if r.sent is not None]
                done_in = sum(1 for r in answered if r.done <= t1)
                line = {
                    "offered_qps": len(reqs) * t.rows / args.seconds,
                    "completed_qps": done_in * t.rows / args.seconds,
                    "backlog_at_close": backlog,
                    "keeps_up": done_in >= 0.95 * len(reqs),
                    "p50_ms": nearest_rank(lat, 50),
                    "p99_ms": nearest_rank(lat, 99),
                    "failed": len(reqs) - len(answered),
                    "generator_late_ms_max": max(late, default=0) * 1e3}
                print(f"[rate] {rate} {json.dumps(line)}", flush=True)
        finally:
            eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
