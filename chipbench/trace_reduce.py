"""From a profiler trace to the numbers the per-layer metrics read.

A trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) has one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds every
operation the chip ran, with its start and duration, and whose ``XLA
Modules`` line holds every program run; the host plane holds the
harness's ``bench.*`` annotations. ``reduce_xplane`` turns it into plain
lists of intervals (:class:`Trace`), and the functions below compute from
those lists alone, so that they can be checked on a small recorded trace
(``chipbench/testdata``) without the profiler.

All times are in seconds from the start of the trace.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

# ops that hold other ops (a scan's loop): their time is their body's
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|"
                        r"all-to-all|collective-permute)")


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, float, float]]       # (op name, start_s, end_s)
    modules: List[Tuple[str, float, float]]   # (program name, start, end)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[str, float, float]]      # (annotation, start, end)
    window: Interval                 # first to last event, device or host

    def to_json(self) -> Dict:
        return {"devices": [{"ops": d.ops, "modules": d.modules}
                            for d in self.devices],
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, j: Dict) -> "Trace":
        return cls([Device([tuple(o) for o in d["ops"]],
                           [tuple(m) for m in d["modules"]])
                    for d in j["devices"]],
                   [tuple(h) for h in j["host"]], tuple(j["window"]))


def op_label(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def program_label(module: str) -> str:
    """``jit_step(7599770094624295516)`` -> ``jit_step``."""
    return module.split("(", 1)[0]


def reduce_xplane(path: str, num_devices: int) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = Device([], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(program_label(e.name), e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name[len("bench."):],
                                     e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    devs = [devices[i] for i in sorted(devices)][:num_devices]
    events = [ev for d in devs for ev in d.ops + d.modules] + host
    window = (min(ev[1] for ev in events), max(ev[2] for ev in events)) \
        if events else (0.0, 0.0)
    return Trace(devs, sorted(host, key=lambda h: h[1]), window)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(dev: Device, window: Interval) -> List[Interval]:
    """Where some operation ran on this chip (program runs where the
    trace holds no operations)."""
    spans = [(s, e) for _, s, e in (dev.ops or dev.modules)]
    return clip(union(spans), window)


def busy_s(trace: Trace, window: Optional[Interval] = None) -> float:
    """Busy seconds, averaged over the chips."""
    w = window or trace.window
    return sum(length(busy(d, w)) for d in trace.devices) \
        / max(len(trace.devices), 1)


def idle_share(trace: Trace, window: Interval) -> float:
    """1 - busy / window, averaged over the chips."""
    span = window[1] - window[0]
    return 1.0 - busy_s(trace, window) / span if span > 0 else 0.0


def program_time(trace: Trace, prefix: str) -> Tuple[float, int]:
    """Device seconds and runs of the programs whose name starts with
    ``prefix`` (``jit_step``), averaged over the chips."""
    tot, runs = 0.0, 0
    for d in trace.devices:
        for name, s, e in d.modules:
            if name.startswith(prefix):
                tot += e - s
                runs += 1
    n = max(len(trace.devices), 1)
    return tot / n, runs // n


def exposed_collective_s(trace: Trace) -> float:
    """Collective seconds during which no other operation ran on that
    chip, averaged over the chips."""
    tot = 0.0
    for d in trace.devices:
        coll = union([(s, e) for name, s, e in d.ops
                      if COLLECTIVE.search(name)])
        other = union([(s, e) for name, s, e in d.ops
                       if not COLLECTIVE.search(name)])
        tot += length(coll) - _overlap(coll, other)
    return tot / max(len(trace.devices), 1)


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The k operations that took most device time, summed over their
    runs and averaged over the chips, loops left out for their bodies:
    ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for name, s, e in d.ops:
            label = op_label(name)
            if CONTAINER.match(label):
                continue
            tot[label] = tot.get(label, 0.0) + (e - s)
    n = max(len(trace.devices), 1)
    return [[name, t / n] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, window: Interval, k: int = 10) -> List[List]:
    """The k longest idle gaps of the first chip inside the window, each
    named by the host annotation that overlaps it most (``host`` where
    none does): ``[[name, seconds], ...]``."""
    if not trace.devices:
        return []
    b = busy(trace.devices[0], window)
    gaps = []
    edges = [window[0]] + [t for iv in b for t in iv] + [window[1]]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((s, e))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        best, best_ov = "host", 0.0
        for name, hs, he in trace.host:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, e - s])
    return out
