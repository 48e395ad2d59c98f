"""What the program's own instrumentation adds to a traced run.

``trace_reduce`` keeps the harness's ``bench.*`` host spans and the
chips' ops and program runs by name. Two readings go further:

* which host span the chip's idle time falls under
  (:func:`idle_by_span`): the idle parts of the window, each put under
  the innermost span open on the host at that moment;
* how much of the fused step's device time lies under each ``psl.*``
  named scope (``psl.client``, ``psl.server``, ``psl.update``;
  :func:`scope_time`). The profiler's op events carry no scope. Its
  ``/host:metadata`` plane holds each program's optimized HLO, whose
  instructions carry it in their metadata
  (``op_name="jit(step)/transpose(jvp(psl.client))/..."``), so the
  scope of an op is looked up by the op's name in its program's HLO
  (:func:`load_scopes`). A fusion takes the scope of its root
  instruction, as XLA gives a fusion its root's metadata.

All times are in seconds, on the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import pathlib
import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import harness, trace_reduce

Span = Tuple[str, float, float]               # (name, start_s, end_s)
Interval = Tuple[float, float]
Scopes = Dict[str, Dict[str, str]]  # program label -> op label -> scope

SCOPE = re.compile(r"\bpsl\.(?:client|server|update)\b")


# ------------------------------------------------- protobuf, by hand

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a slice of ``buf`` for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, val


def _sub(buf, number: int) -> Iterator[object]:
    return (v for f, v in _fields(buf) if f == number)


def hlo_scopes(xspace: bytes) -> Scopes:
    """Per program label, every HLO instruction's ``psl.*`` scope (``""``
    where it has none), from the ``/host:metadata`` plane of an
    ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``: plane 1, its name
    2, event metadata 4 -> value 2 -> name 2 and stats 5 -> bytes 6 =
    ``HloProto``; ``xla/service/hlo.proto``: module 1 -> computations 3
    -> instructions 2 -> name 1 and metadata 7 -> op_name 2)."""
    out: Scopes = {}
    for plane in _sub(memoryview(xspace), 1):
        name = next(_sub(plane, 2), b"")
        if bytes(name) != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                label = trace_reduce.program_label(
                    bytes(next(_sub(meta, 2), b"")).decode())
                table = out.setdefault(label, {})
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        _instruction_scopes(proto, table)
    return out


def _instruction_scopes(hlo_proto, table: Dict[str, str]) -> None:
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for ins in _sub(comp, 2):
                name = bytes(next(_sub(ins, 1), b"")).decode()
                scope = ""
                for meta in _sub(ins, 7):
                    op_name = bytes(next(_sub(meta, 2), b"")).decode()
                    m = SCOPE.search(op_name)
                    if m:
                        scope = m.group(0)
                table[name] = scope


def _step_ops(dev: trace_reduce.Device,
              prefix: str) -> Iterator[Tuple[str, str, float]]:
    """``(program label, op label, seconds)`` of the ops that ran inside
    the runs of the programs named by ``prefix``, loops left out for
    their bodies."""
    runs = sorted(((s, e, m) for m, s, e in dev.modules
                   if m.startswith(prefix)))
    starts = [s for s, _, _ in runs]
    for name, s, e in dev.ops:
        op = trace_reduce.op_label(name)
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1] or trace_reduce.CONTAINER.match(op):
            continue
        yield runs[i][2], op, e - s


def load_scopes(trace: trace_reduce.Trace,
                prefix: str) -> Optional[Scopes]:
    """The HLO scopes of the profile that ``trace`` was reduced from: the
    newest ``.xplane.pb`` the harness wrote, if every op that ran in the
    first chip's runs of ``prefix`` is an instruction of that program
    there. None when there is no such file."""
    if not trace.devices:
        return None
    files = glob.glob(str(harness.BENCH_DIR / ".traces" / "**"
                          / "*.xplane.pb"), recursive=True)
    if not files:
        return None
    scopes = hlo_scopes(pathlib.Path(max(files, key=os.path.getmtime))
                        .read_bytes())
    ran = [(prog, op) for prog, op, _ in _step_ops(trace.devices[0], prefix)]
    if not ran or any(op not in scopes.get(prog, {}) for prog, op in ran):
        return None
    return scopes


# ------------------------------------------------------- host spans

def innermost(spans: List[Span], window: Interval) -> List[Span]:
    """The window tiled by the innermost span open in each part (the one
    opened last among those open), ``""`` where none is:
    ``[(name, start, end), ...]`` in order."""
    bounds = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    lo, hi = window
    out: List[Span] = []
    opened: List[int] = []
    t = lo
    for when, starts, i in bounds:
        when = min(max(when, lo), hi)
        if when > t:
            out.append((spans[opened[-1]][0] if opened else "", t, when))
            t = when
        if starts:
            opened.append(i)
        else:
            opened.remove(i)
    if t < hi:
        out.append((spans[opened[-1]][0] if opened else "", t, hi))
    return out


def idle_by_span(busy: List[Interval], spans: List[Span],
                 window: Interval) -> Dict[str, float]:
    """Seconds of the window in which the chip is idle (not in ``busy``,
    a sorted disjoint list), by the innermost span open on the host at
    that moment (``""`` where none is)."""
    out: Dict[str, float] = {}
    busy = trace_reduce.clip(busy, window)
    j = 0
    for name, s, e in innermost(spans, window):
        covered = 0.0
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            covered += min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
        out[name] = out.get(name, 0.0) + (e - s) - covered
    return out


# ------------------------------------------------------- device scopes

def scope_time(dev: trace_reduce.Device, scopes: Scopes, prefix: str,
               scope: str) -> float:
    """Device seconds of the ops under ``scope`` (``""``: under none) in
    the runs of the programs whose name starts with ``prefix``; loops are
    left out for their bodies."""
    return sum(t for prog, op, t in _step_ops(dev, prefix)
               if scopes.get(prog, {}).get(op, "") == scope)
