#!/usr/bin/env python
"""Summarize a repro.obs trace file (Chrome trace JSON or events JSONL).

Renders, for either export format a traced run writes
(``obs.trace_path`` → Chrome trace-event JSON, ``obs.events_path`` →
structured JSONL):

* run metadata (the tracer's ``meta``: train/serve, protocol/engine);
* a **phase breakdown** — per span name: count, total time, and
  mean/p50/p95/p99 durations (training: plan/batch/step/callbacks/eval;
  serving: admit/decode_step/wait);
* **request lifecycles** (serving traces) — per-phase
  enqueue/prefill/decode durations and end-to-end request latency,
  reconstructed from the async begin/end pairs;
* **counter** ranges (active_slots, queued);
* **speculative draft windows** (speculative-engine ``spec_window``
  records) — per-request accepted/rejected proposal totals and the
  overall acceptance rate;
* the **GPSL monitor verdict** (JSONL only — monitor records never enter
  the Chrome timeline): per-epoch violation counts and the worst step's
  class deviation vs the Serfling radius.

Usage:
  python tools/trace_report.py trace.json
  python tools/trace_report.py events.jsonl
  python tools/trace_report.py trace.json --json     # machine-readable

Stdlib-only on purpose: it must run anywhere the artifacts land, with no
repository on PYTHONPATH. For the interactive twin, load the same
trace.json in Perfetto (https://ui.perfetto.dev, *Open trace file*).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from collections import defaultdict
from typing import Any, Dict, List


def _percentiles(xs: List[float]) -> Dict[str, float]:
    """mean/p50/p95/p99/max with linear interpolation (numpy-compatible)."""
    if not xs:
        return {k: 0.0 for k in ("mean", "p50", "p95", "p99", "max")}
    s = sorted(xs)

    def pct(q: float) -> float:
        pos = q / 100.0 * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        return s[lo] + (s[hi] - s[lo]) * (pos - lo)

    return {"mean": sum(s) / len(s), "p50": pct(50.0), "p95": pct(95.0),
            "p99": pct(99.0), "max": s[-1]}


def load_rows(path: pathlib.Path) -> List[Dict[str, Any]]:
    """Normalize either export format to JSONL-shaped rows.

    Rows: ``{"kind": meta|span|instant|counter|async_begin|async_end|
    record-kinds..., "name", "cat", "ts_s", ["dur_s"], ["id"], ["args"]}``
    — the JSONL schema; Chrome trace events are converted into it.
    """
    text = path.read_text()
    try:
        doc = json.loads(text)          # one document → Chrome trace JSON
    except json.JSONDecodeError:
        doc = None                      # many lines → events JSONL
    if isinstance(doc, dict) and "traceEvents" in doc:
        kind = {"X": "span", "i": "instant", "C": "counter",
                "b": "async_begin", "e": "async_end"}
        rows: List[Dict[str, Any]] = [
            {"kind": "meta", "meta": doc.get("otherData", {})}]
        for ev in doc.get("traceEvents", []):
            row: Dict[str, Any] = {"kind": kind.get(ev["ph"], ev["ph"]),
                                   "name": ev["name"], "cat": ev["cat"],
                                   "ts_s": ev["ts"] / 1e6}
            if ev["ph"] == "X":
                row["dur_s"] = ev["dur"] / 1e6
            if "id" in ev:
                row["id"] = ev["id"]
            if "args" in ev:
                row["args"] = ev["args"]
            rows.append(row)
        return rows
    return [json.loads(line) for line in text.splitlines() if line.strip()]


def summarize(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The report document ``main`` renders (also the ``--json`` output)."""
    meta: Dict[str, Any] = {}
    spans: Dict[str, List[float]] = defaultdict(list)
    counters: Dict[str, List[float]] = defaultdict(list)
    begins: Dict[tuple, float] = {}
    lifecycle: Dict[str, List[float]] = defaultdict(list)
    monitor_steps: List[Dict[str, Any]] = []
    monitor_summaries: List[Dict[str, Any]] = []
    spec_windows: List[Dict[str, Any]] = []
    for r in rows:
        k = r.get("kind")
        if k == "meta":
            meta = r.get("meta", {k2: v for k2, v in r.items()
                                  if k2 != "kind"})
        elif k == "span":
            spans[r["name"]].append(float(r.get("dur_s", 0.0)))
        elif k == "counter":
            counters[r["name"]].append(float(r["args"]["value"]))
        elif k == "async_begin":
            begins[(r["name"], r.get("id"))] = float(r["ts_s"])
        elif k == "async_end":
            t0 = begins.pop((r["name"], r.get("id")), None)
            if t0 is not None:
                lifecycle[r["name"]].append(float(r["ts_s"]) - t0)
        elif k == "monitor":
            monitor_steps.append(r)
        elif k == "monitor_summary":
            monitor_summaries.append(r)
        elif k == "spec_window":
            spec_windows.append(r)
    out: Dict[str, Any] = {"meta": meta}
    out["phases"] = {
        name: {"count": len(ds), "total_s": sum(ds),
               **{k2: v for k2, v in _percentiles(ds).items()}}
        for name, ds in sorted(spans.items())}
    if lifecycle:
        out["requests"] = {
            name: {"count": len(ds), **_percentiles(ds)}
            for name, ds in sorted(lifecycle.items())}
    if counters:
        out["counters"] = {
            name: {"samples": len(vs), "min": min(vs), "max": max(vs),
                   "last": vs[-1]}
            for name, vs in sorted(counters.items())}
    if spec_windows:
        # per-request accepted/rejected draft spans (speculative engine
        # spec_window records — JSONL only, like the monitor records)
        per_rid: Dict[Any, Dict[str, int]] = {}
        for w in spec_windows:
            row = per_rid.setdefault(
                w.get("rid"), {"windows": 0, "proposed": 0, "accepted": 0})
            row["windows"] += 1
            row["proposed"] += int(w.get("proposed", 0))
            row["accepted"] += int(w.get("accepted", 0))
        proposed = sum(r["proposed"] for r in per_rid.values())
        accepted = sum(r["accepted"] for r in per_rid.values())
        out["speculation"] = {
            "windows": len(spec_windows),
            "proposed": proposed, "accepted": accepted,
            "rejected": proposed - accepted,
            "acceptance_rate": accepted / proposed if proposed else 0.0,
            "per_request": {
                str(rid): dict(
                    row, acceptance_rate=(row["accepted"] / row["proposed"]
                                          if row["proposed"] else 0.0))
                for rid, row in sorted(per_rid.items())}}
    if monitor_summaries or monitor_steps:
        viols = [m for m in monitor_steps
                 if not (m.get("deviation_ok", True)
                         and m.get("batch_fixed", True)
                         and not m.get("overdraw", 0))]
        out["monitor"] = {"epochs": monitor_summaries,
                          "violations": viols,
                          "ok": all(m.get("ok", False)
                                    for m in monitor_summaries)
                          and not viols}
    return out


def _fmt_s(x: float) -> str:
    return f"{x * 1e3:8.2f}ms"


def render(doc: Dict[str, Any]) -> str:
    lines: List[str] = []
    meta = doc.get("meta") or {}
    if meta:
        lines.append("meta: " + ", ".join(f"{k}={v}"
                                          for k, v in meta.items()))
    if doc.get("phases"):
        lines.append("")
        lines.append(f"{'phase':>14} {'count':>6} {'total':>10} "
                     f"{'mean':>10} {'p50':>10} {'p95':>10} {'p99':>10}")
        for name, p in doc["phases"].items():
            lines.append(f"{name:>14} {p['count']:>6} {_fmt_s(p['total_s'])}"
                         f" {_fmt_s(p['mean'])} {_fmt_s(p['p50'])}"
                         f" {_fmt_s(p['p95'])} {_fmt_s(p['p99'])}")
    if doc.get("requests"):
        lines.append("")
        lines.append(f"{'lifecycle':>14} {'count':>6} {'mean':>10} "
                     f"{'p50':>10} {'p95':>10} {'p99':>10} {'max':>10}")
        for name, p in doc["requests"].items():
            lines.append(f"{name:>14} {p['count']:>6} {_fmt_s(p['mean'])}"
                         f" {_fmt_s(p['p50'])} {_fmt_s(p['p95'])}"
                         f" {_fmt_s(p['p99'])} {_fmt_s(p['max'])}")
    if doc.get("counters"):
        lines.append("")
        for name, c in doc["counters"].items():
            lines.append(f"counter {name}: min={c['min']:g} max={c['max']:g}"
                         f" last={c['last']:g} ({c['samples']} samples)")
    if doc.get("speculation"):
        sp = doc["speculation"]
        lines.append("")
        lines.append(
            f"speculative draft windows: {sp['windows']} "
            f"(proposed {sp['proposed']}, accepted {sp['accepted']}, "
            f"rejected {sp['rejected']}, "
            f"acceptance {sp['acceptance_rate']:.3f})")
        lines.append(f"{'rid':>6} {'windows':>8} {'proposed':>9} "
                     f"{'accepted':>9} {'accept%':>8}")
        for rid, row in sp["per_request"].items():
            lines.append(f"{rid:>6} {row['windows']:>8} "
                         f"{row['proposed']:>9} {row['accepted']:>9} "
                         f"{100.0 * row['acceptance_rate']:>7.1f}%")
    if "monitor" in doc:
        mon = doc["monitor"]
        lines.append("")
        lines.append("GPSL monitor: " + ("OK" if mon["ok"] else "VIOLATIONS"))
        for ep in mon["epochs"]:
            lines.append(
                f"  epoch {ep.get('epoch')}: steps={ep.get('steps')} "
                f"dev={ep.get('deviation_violations')} "
                f"batch={ep.get('batch_size_violations')} "
                f"overdraw={ep.get('overdraw_violations')} "
                f"residual={ep.get('residual_mass')} "
                f"max_dev={ep.get('max_class_deviation', 0.0):.4f} "
                f"(eps={ep.get('epsilon', 0.0):.4f}, "
                f"worst step {ep.get('worst_step')})")
        for v in mon["violations"][:10]:
            lines.append(f"  VIOLATION epoch {v.get('epoch')} "
                         f"step {v.get('step')}: "
                         f"max_dev={v.get('max_class_deviation', 0.0):.4f} "
                         f"eps={v.get('epsilon', 0.0):.4f} "
                         f"batch={v.get('batch')} "
                         f"overdraw={v.get('overdraw')}")
        extra = len(mon["violations"]) - 10
        if extra > 0:
            lines.append(f"  ... and {extra} more violating steps")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="trace.json (Chrome trace-event) or "
                                  "events.jsonl (structured log)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the summary as JSON instead of a table")
    args = ap.parse_args(argv)
    doc = summarize(load_rows(pathlib.Path(args.trace)))
    if args.as_json:
        print(json.dumps(doc, indent=2))
    else:
        print(render(doc))
    mon = doc.get("monitor")
    return 1 if (mon is not None and not mon["ok"]) else 0


if __name__ == "__main__":
    sys.exit(main())
