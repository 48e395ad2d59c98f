#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration file,
its traffic file (``chipbench/traffic/<traffic>.json``), the driver that
file names (``chipbench/drivers/<driver>.py``) and each per-layer
metric's reader (``chipbench/metrics/<metric>.py``) are found by their
names, so a cell, a traffic mix or a metric is added by files and
entries alone.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, in a traced run ``breakdown``, and last ``checks``: each
number compared for ``correct`` beside its limit, which also end the
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits with code 3 and prints no result.

``--control`` and ``--fault`` are for measuring the limits and testing
the comparison: the first also computes the control's readings (the
reference in the precision below the configuration's), the second
breaks the timed path (``unchanged``, ``half_batch``, ``token``).
``--rate`` overrides a serving cell's arrival rate, for the sweep that
finds the knee.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE.parent))
sys.path.insert(0, str(_HERE.parent / "src"))

from chipbench import harness  # noqa: E402


def cell_plan(bench, name: str, traffic_dir=None):
    """The cell, its configuration, its traffic (from ``traffic_dir``,
    ``chipbench/traffic`` by default) and the names of the end-to-end and
    per-layer metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = harness.load_json(harness.ROOT / configs[cell["config"]]["file"])
    traffic = harness.load_json(pathlib.Path(
        traffic_dir or harness.BENCH_DIR / "traffic")
        / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m["name"] for m in bench["end_to_end"] if mine(m)]
    per_layer = [m["name"] for m in bench["per_layer"] if mine(m)]
    return cell, config, traffic, e2e, per_layer


def run_cell(bench, name: str, seed: int, seconds: float, trace: bool,
             options=None, check_chips: bool = True, traffic_dir=None,
             metrics_dir=None):
    """One run of a cell: the result dict the last line prints, and the
    numbers compared for ``correct``."""
    options = dict(options or {})
    cell, config, traffic, e2e, per_layer = cell_plan(bench, name, traffic_dir)
    if check_chips:
        harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{traffic['driver']}.py",
        f"chipbench_driver_{traffic['driver']}")
    with harness.CompileMeter() as meter:
        options["meter"] = meter
        out = driver.run(cell, config, traffic, seed, seconds, trace,
                         options)
    if trace:
        metrics = harness.read_per_layer(per_layer, out["record"],
                                         metrics_dir)
    else:
        metrics = {k: out["e2e"][k] for k in e2e}
    device = out["device"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    tr = out["record"].get("trace")
    if trace and tr is not None and tr.devices:
        from chipbench import trace_reduce
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window[1] - tr.window[0]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr),
            "idle_gaps": trace_reduce.idle_gaps(tr, tr.window)}
    result["details"] = dict(out.get("extra", {}),
                             compiles_in_window=out["compiles_in_window"],
                             seed=seed)
    return result, out["checks"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("unchanged", "half_batch", "token"))
    ap.add_argument("--rate", type=float)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    try:
        result, checks = run_cell(
            bench, args.workload, args.seed, args.seconds, bool(args.trace),
            {"control": args.control, "fault": args.fault,
             "rate": args.rate})
    except harness.NoChip as e:
        print(f"chipbench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
