"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one cell is found by name: the cell's entry
in ``BENCHMARK.json`` and its file ``bench/workloads/<cell>.json``, the
deployment ``bench/configs/<config>.json``, the traffic generator
``bench/traffic/<kind>.py`` and one reader ``bench/metrics/<metric>.py``
per per-layer metric (the part of its name before the first dot).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path


BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 60.0       # how long after the close the run waits for answers


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    spec: dict           # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    end_to_end: list     # BENCHMARK.json end-to-end metrics of this cell
    per_layer: list      # BENCHMARK.json per-layer metrics of this cell


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its files, checked against each other."""
    bench = _load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell named {name!r} in BENCHMARK.json")
    entry = entries[name]
    spec = _load_json(BENCH / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {spec[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    config = _load_json(BENCH / "configs" / f"{entry['config']}.json")
    return Cell(name, entry, spec, config,
                [m for m in bench["end_to_end"] if _in_cell(m, name)],
                [m for m in bench["per_layer"] if _in_cell(m, name)])


def traffic_module(kind: str):
    return _module(BENCH / "traffic" / f"{kind}.py", f"bench_traffic_{kind}")


def metric_reader(metric_name: str):
    base = metric_name.split(".")[0]
    return _module(BENCH / "metrics" / f"{base}.py", f"bench_metric_{base}")


def require_devices(chips: int):
    """The devices of a run on ``chips`` TPU chips, or NoDevice."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devs)} "
                       f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class GcMeter:
    """Pauses of Python's garbage collector, in seconds."""

    def __init__(self):
        self.pauses: list = []
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None


def _read_counters() -> dict:
    """Counters of the process and its machine that a stall could come
    from, each a running total: the process's CPU seconds and page faults,
    the machine's iowait and steal seconds summed over its cores, the
    seconds in which some task waited for a CPU, on memory or on I/O
    (pressure stall information), and the times the cgroup's memory went over its high or
    max mark. A counter this kernel does not keep is left out."""
    out = {"cpu_s": time.process_time()}
    try:
        with open("/proc/self/stat") as f:
            st = f.read().rsplit(")", 1)[1].split()
        out["minflt"], out["majflt"] = int(st[7]), int(st[9])
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        tick = os.sysconf("SC_CLK_TCK")
        out["iowait_s"] = int(cpu[5]) / tick
        out["steal_s"] = int(cpu[8]) / tick
    except (OSError, IndexError, ValueError):
        pass
    for res in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{res}") as f:
                for line in f:
                    kind, *fields = line.split()
                    total = dict(x.split("=") for x in fields)["total"]
                    out[f"psi_{res}_{kind}_s"] = int(total) / 1e6
        except (OSError, KeyError, ValueError):
            pass
    try:
        with open("/sys/fs/cgroup/memory.events") as f:
            for line in f:
                key, val = line.split()
                if key in ("high", "max"):
                    out[f"memcg_{key}"] = int(val)
    except (OSError, ValueError):
        pass
    return out


class StallWatch:
    """Stalls of the whole process in the window: times when no Python
    thread could run (the GIL held by a long call, or the process not
    scheduled). A heartbeat thread re-arms ``faulthandler``'s watchdog
    every ``beat`` seconds; where it could not for ``after`` seconds, the
    watchdog's own C thread, which needs no GIL, writes every thread's
    stack to ``path``. ``gap_max`` is the longest heartbeat gap. Each gap
    over ``after`` is kept in ``stalls``: when it began (``at_s``, seconds
    after ``start``) and what the counters of ``_read_counters`` moved
    meanwhile. The process's CPU seconds near the gap mean a thread
    computed holding the GIL; near 0, the process waited with it held."""

    def __init__(self, path, after=0.5, beat=0.05):
        import threading
        self.path, self.after, self.beat = path, after, beat
        self.gap_max = 0.0
        self.stalls: list = []
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import faulthandler
        self.t_start = t_start = last = time.perf_counter()
        c0 = _read_counters()
        while not self._stop.is_set():
            faulthandler.dump_traceback_later(self.after, file=self._f)
            now, c1 = time.perf_counter(), _read_counters()
            gap = now - last
            self.gap_max = max(self.gap_max, gap)
            if gap > self.after:
                st = {"at_s": last - t_start, "gap_s": gap}
                st.update({k: v - c0[k] for k, v in c1.items() if k in c0})
                self.stalls.append(st)
            last, c0 = now, c1
            self._stop.wait(self.beat)

    def start(self):
        self._f = open(self.path, "w")
        self._th.start()

    def stop(self) -> str:
        """Stop watching; the stacks written at each stall."""
        import faulthandler
        self._stop.set()
        self._th.join()
        faulthandler.cancel_dump_traceback_later()
        self._f.close()
        with open(self.path) as f:
            return f.read()


def written_bytes() -> int:
    """Bytes this process has caused to be written to storage."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


class CompileMeter:
    """Counts JAX's backend compiles and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.secs = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

def engine_module(name: str):
    """``bench/engines/<name>.py``: the system a cell drives, built by its
    ``build(config, lane, capacity, disk_path, seed, base, attrs)``."""
    return _module(BENCH / "engines" / f"{name}.py", f"bench_engine_{name}")


class Updates:
    """What an update stream inserts, made on demand, and every vector
    stored by id (the reference's host copy). Ids ``n0`` to ``capacity``
    are the deployment's room for inserts."""

    def __init__(self, seed, config, n0, capacity, by_id):
        self.seed, self.n0, self.capacity, self.by_id = (seed, n0, capacity,
                                                         by_id)
        self.dim, self.latent = config["dim"], config["latent_dim"]
        self.schema = config["attributes"]
        self.warm_ops: list = []

    def fresh(self, step, rows):
        """The vectors and attributes inserted at update ``step``."""
        from bench.data import fresh_batch
        return fresh_batch(self.seed, step, rows, self.dim, self.latent,
                           self.schema)

    def remember(self, ids, vecs):
        self.by_id[ids] = vecs


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices=None, make_engine=None,
             config_override=None, log=print) -> dict:
    """Set up, measure ``seconds``, check, and return the result line.

    ``devices`` are the chips in use (None: the test's CPU run, which
    reports no device metric). ``make_engine`` stands in for the cell's
    engine builder (the control)."""
    from jax.profiler import TraceAnnotation

    from bench import checks, trace_reduce
    from bench.data import make_attributes, make_vectors
    from bench.reference import Reference

    config = dict(cell.config, **(config_override or {}))
    lane = cell.spec["lane"]
    meter = CompileMeter()
    kind = cell.spec["traffic_kind"]
    traffic = traffic_module(kind).Traffic(
        cell.spec["traffic_params"], seconds, seed, config["max_batch"])
    n0, dim = config["n"], config["dim"]
    n_q = traffic.query_rows()
    capacity = n0 + traffic.insert_room()
    x = make_vectors(seed, n0 + n_q, dim, config["latent_dim"])
    attrs = make_attributes(seed, n0, config["attributes"])
    base, queries = x[:n0], x[n0:]
    del x
    ref = Reference(base, capacity)
    updates = Updates(seed, config, n0, capacity, ref.host)
    t_data = time.perf_counter()
    log(f"[set-up] data rows={n0 + n_q} dim={dim} capacity={capacity} "
        f"s={t_data - t_start:.3f}")

    with tempfile.TemporaryDirectory(prefix="svf-bench-") as work:
        c0, h0 = meter.compiles, meter.hits
        make = make_engine or engine_module(cell.spec["engine"]).build
        engine = make(config, lane, capacity, os.path.join(work, "index"),
                      seed, base, attrs)
        t_built = time.perf_counter()
        log(f"[set-up] build s={t_built - t_data:.3f} "
            f"compiles={meter.compiles - c0} cache_hits={meter.hits - h0}")
        try:
            traffic.warm(engine, queries, updates)
            t_warm = time.perf_counter()
            log(f"[set-up] warm s={t_warm - t_built:.3f} "
                f"compiles={meter.compiles - c0} "
                f"cache_hits={meter.hits - h0}")
            stats0 = engine.stats()
            gcm = GcMeter()
            stall = StallWatch(os.path.join(work, "stalls.txt"))
            stall.start()
            w0 = written_bytes()
            tracer = (trace_reduce.Tracer(os.path.join(work, "trace"))
                      if trace else None)
            if tracer:
                tracer.start()
            c1 = meter.compiles
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            with TraceAnnotation(trace_reduce.WINDOW_SPAN):
                reqs, ops = traffic.run(engine, queries, updates, t0,
                                        seconds)
            t1 = t0 + seconds
            drained = time.perf_counter()
            in_window = meter.compiles - c1
            stacks = stall.stop()
            gc.callbacks.remove(gcm._cb)
            w1 = written_bytes()
            if tracer:
                tracer.stop()
            stats1 = engine.stats()
            dev = device_info(devices) if devices else None
        finally:
            engine.close()
            del engine
            gc.collect()
        log(f"[window] seconds={seconds} requests={len(reqs)} ops={len(ops)}"
            f" drained_after_close_s={max(0.0, drained - t1):.3f} "
            f"compiles_in_window={in_window} gc_pauses={len(gcm.pauses)} "
            f"gc_pause_max_ms={max(gcm.pauses, default=0.0) * 1e3:.3f} "
            f"written_bytes_setup={w0} written_bytes_window={w1 - w0} "
            f"heartbeat_gap_max_ms={stall.gap_max * 1e3:.1f} "
            f"watch_start_clock={stall.t_start:.3f}")
        notes = getattr(traffic, "notes", dict)()
        if notes:
            log("[window] " + " ".join(f"{k}={v}" for k, v in notes.items()))
        for st in stall.stalls:
            log("[stall] " + " ".join(f"{k}={v:.3f}" for k, v in st.items()))
        if stacks:
            log(f"[stall] stacks of every thread after "
                f"{stall.after * 1e3:.0f} ms without a heartbeat "
                f"({stacks.count('Timeout (')} stalls), the first:\n"
                f"{stacks[:6000]}")
        reduced = tracer.reduce() if tracer else None

    errors = [r.error for r in reqs if r.error is not None] + [
        o.error for o in ops if o.error is not None]
    if errors:
        log(f"[window] errors={len(errors)} first={errors[0]!r}")
    t_check = time.perf_counter()
    verdict = checks.judge_window(cell, config, ref, reqs,
                                  updates.warm_ops, ops, n0, t0, t1)
    ref.free()
    log(f"[check] s={time.perf_counter() - t_check:.3f}")
    e2e = checks.end_to_end(reqs, ops, verdict, t0, t1, setup_s,
                            drained + DRAIN_S, log)
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"]}
    if trace:
        ctx = {"stats0": stats0, "stats1": stats1, "trace": reduced,
               "e2e": e2e,
               "cell": cell, "config": config,
               "device_kind": devices[0].device_kind if devices else None}
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    if dev is not None:
        if reduced is not None:
            dev["busy_s"], dev["window_s"] = reduced.busy_s, reduced.window_s
        result["device"] = dev
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = verdict["checks"]
    return result


def print_checks(checks: dict, stream=sys.stderr):
    for name, c in checks.items():
        print(f"[check] {name}={c['value']} limit {c['limit']}", file=stream)
