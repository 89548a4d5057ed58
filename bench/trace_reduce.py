"""From the profiler's trace of the window to numbers.

``Tracer`` records the window with ``jax.profiler`` (its default host
and Python tracers on: the Python frames are what names an idle gap).
``extract`` reads the ``.xplane.pb`` it wrote into plain lists: the
device operations of each TPU (plane ``/device:TPU:<i>``, line ``XLA
Ops``, each named by its HLO text, ``%pq_adc.4 = f32[256,1,512] ...``)
and the host events (plane ``/host:CPU``) of 50 us or more, with every
``bench.*`` span, all on the trace's one clock. ``Reduced`` takes those
lists and the window, which is the benchmark's own host span
``bench.window``, and gives:

- ``busy_s``: the union of the intervals in which an operation ran on
  the device, averaged over the chips, and ``window_s``;
- ``kernel_calls(name)``: each call of the operation ``%<name>.<n>`` (a
  Pallas kernel is named after its jitted wrapper), with its device time
  and the array shapes of its HLO text;
- ``span_busy_share(names)``: the device-busy share inside the host spans
  of those names;
- ``breakdown()``: the device operations that took the most time, and
  the longest idle gaps, each named by the innermost host event that
  covers at least half of it and is not a wait.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
HOST_MIN_NS = 50e3
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|"
                    r"f64)\[([0-9,]*)\]")
# control flow holds the operations it runs: not an operation of its own
_CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
# a host event that only waits says nothing of what the host was doing
_WAITING = re.compile(r"wait|acquire|sleep|join|queue\.py:\d+ get|select|"
                      r"poll|^bench\.(window|wait|search)$")


class Tracer:
    def __init__(self, log_dir):
        self.log_dir = log_dir

    def start(self):
        import jax
        jax.profiler.start_trace(self.log_dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self):
        paths = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        return Reduced(extract(max(paths, key=os.path.getmtime)))


def extract(path):
    """{"devices": {plane: [(name, start_ns, dur_ns)]},
        "host": [(line, name, start_ns, dur_ns)]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if (e.duration_ns >= HOST_MIN_NS
                            or e.name.startswith("bench.")):
                        host.append((line.name, e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"devices": devices, "host": host}


def op_name(text):
    """``pq_adc.4`` of ``%pq_adc.4 = f32[...] custom-call(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_base(text):
    """``pq_adc`` of ``%pq_adc.4 = ...``."""
    return re.sub(r"\.\d+$", "", op_name(text))


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged, lo, hi):
    return sum(e - s for s, e in clip(merged, lo, hi))


def shapes(text):
    """(dtype, dims) of every array shape in an operation's HLO text:
    its result first, then its operands."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


class Reduced:
    def __init__(self, ev):
        self.ev = ev
        spans = [(s, s + d) for _, n, s, d in ev["host"] if n == WINDOW_SPAN]
        if not spans or not ev["devices"]:
            raise ValueError("the trace holds no window span or no TPU")
        self.w0, self.w1 = max(spans, key=lambda x: x[1] - x[0])
        self.busy = {p: union(clip([(s, s + d) for _, s, d in ops],
                                   self.w0, self.w1))
                     for p, ops in ev["devices"].items()}

    @property
    def window_s(self):
        return (self.w1 - self.w0) * 1e-9

    @property
    def busy_s(self):
        return (sum(covered(b, self.w0, self.w1) for b in self.busy.values())
                / len(self.busy) * 1e-9)

    def kernel_calls(self, name):
        """[(seconds, shapes)] of the window's calls of operation ``name``."""
        return [(d * 1e-9, shapes(text))
                for ops in self.ev["devices"].values()
                for text, s, d in ops
                if self.w0 <= s < self.w1 and op_base(text) == name]

    def spans(self, names):
        return [(s, s + d) for _, n, s, d in self.ev["host"]
                if n in names and s < self.w1 and s + d > self.w0]

    def span_busy_share(self, names):
        """Device-busy share of the time inside the named host spans."""
        sp = union(clip(self.spans(names), self.w0, self.w1))
        total = sum(e - s for s, e in sp)
        if total <= 0:
            return None
        busy = sum(covered(b, s, e) for b in self.busy.values()
                   for s, e in sp) / len(self.busy)
        return busy / total

    def _host_doing(self, g0, g1, host):
        inner, widest, cover = None, "no host event", 0.0
        for n, s, e in host:
            c = min(e, g1) - max(s, g0)
            if c <= 0:
                continue
            if c >= 0.5 * (g1 - g0) and (inner is None or e - s < inner[1]):
                inner = (n, e - s)
            if c > cover:
                widest, cover = n, c
        return inner[0] if inner else widest

    def breakdown(self, top=10):
        per_op = defaultdict(float)
        for ops in self.ev["devices"].values():
            for text, s, d in ops:
                name = op_name(text)
                if self.w0 <= s < self.w1 and not _CONTAINER.match(name):
                    per_op[name] += d * 1e-9 / len(self.busy)
        gaps = []
        for b in self.busy.values():
            edges = [self.w0] + [x for iv in b for x in iv] + [self.w1]
            gaps += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        host = [(n, s, s + d) for _, n, s, d in self.ev["host"]
                if d > 0 and not _WAITING.search(n)]
        named = [[self._host_doing(g0, g1, host), (g1 - g0) * 1e-9]
                 for g0, g1 in gaps[:top]]
        ops = sorted(per_op.items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": named}
