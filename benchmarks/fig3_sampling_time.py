#!/usr/bin/env python
"""Paper Fig. 3: wall-clock epoch-plan sampling time, UGS vs LDS(Δ), vs K.

Three claims are measured:

1. Paper fidelity (small K): LDS stays only slightly slower than UGS —
   the paper's low-overhead claim.
2. Planner-engine scaling (the repo's extension): the jit-compiled JAX
   planner (``backend="jax"``, src/repro/core/planner.py) against the NumPy
   reference across a K-sweep up to 65536 clients. The ``speedup_x`` derived
   field is the ratio on the host that runs it, not evidence of speed on
   the chip (LDS cells are dominated by the on-device MAP-EM replanning;
   UGS cells are bounded by the dense (T, K) plan materialization that
   both backends share).
3. Million-client sparse planning (``plan_format="sparse"``): a K-sweep
   through 1e6 clients with plan-bytes and peak-RSS columns, written as
   JSON to ``--out`` when given. Sparse plans store per-step active-client
   segments — O(T·B) memory — so plan bytes per client *fall* as K grows
   past B.

Timing convention (audited): every jit-backed cell pays its one-time
compile in an untimed warmup call and reports the best of N steady-state
runs — the cost a trainer pays when replanning every epoch with the
compiled executable cached. NumPy cells are warmed once (page/allocator
effects) and report best-of-N at small K; the expensive large-K reference
cells are timed once (their cost is deterministic).

Usage:
  PYTHONPATH=src python benchmarks/fig3_sampling_time.py           # full
  PYTHONPATH=src python benchmarks/fig3_sampling_time.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import pathlib
import resource
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np                                         # noqa: E402

from repro.core import (ClientPopulation, assign_delays,   # noqa: E402
                        lds_plan, ugs_plan)
from benchmarks.table4_tpe import _pop                     # noqa: E402
from benchmarks.common import Csv, time_us                 # noqa: E402


def _sweep_pop(k: int, per: int, seed: int = 0, m: int = 10
               ) -> ClientPopulation:
    """Large-K federation: D_k ~ per + U(0, per/2), mildly non-IID classes."""
    rng = np.random.default_rng(seed)
    sizes = np.full(k, per, np.int64) + rng.integers(0, max(per // 2, 1), k)
    major = rng.integers(0, m, k)
    counts = np.zeros((k, m), np.int64)
    probs = np.full((m, m), 0.05) + np.eye(m) * 0.50
    probs /= probs.sum(axis=1, keepdims=True)
    for i in range(k):
        counts[i] = rng.multinomial(sizes[i], probs[major[i]])
    return ClientPopulation(sizes, counts, np.zeros(k))


def _edge_pop(k: int, lo: int, hi: int, seed: int = 0, m: int = 4
              ) -> ClientPopulation:
    """Cross-device-scale federation: tiny local datasets (lo..hi-1
    samples each), one major class per client. Cheap to build at K = 1e6
    (no per-client multinomial loop)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(lo, hi, size=k).astype(np.int64)
    counts = np.zeros((k, m), np.int64)
    counts[np.arange(k), rng.integers(0, m, k)] = sizes
    return ClientPopulation(sizes, counts, np.zeros(k))


def _sweep_cell(csv: Csv, name: str, k: int, plan_np, plan_jax,
                jax_repeat: int = 2):
    # jax: untimed warmup call pays the compile, then best-of steady-state;
    # numpy: timed once (deterministic cost, expensive at large K)
    us_jax = time_us(lambda: plan_jax(1), repeat=jax_repeat, warmup=1,
                     best=True)
    us_np = time_us(lambda: plan_np(0), repeat=1, warmup=0)
    csv.add(f"fig3_planner_sweep[{name},K={k},numpy]", us_np,
            f"seconds={us_np/1e6:.2f}")
    csv.add(f"fig3_planner_sweep[{name},K={k},jax]", us_jax,
            f"seconds={us_jax/1e6:.2f};speedup_x={us_np/us_jax:.1f}")


# K → (lo, hi, B) geometry of the sparse-plan sweep: local datasets shrink
# as the federation grows (the cross-device regime that motivates K = 1e6),
# keeping T = ⌈D/B⌉ a few hundred steps in every cell.
_SPARSE_SWEEP = {
    4096: (4, 9, 128),
    65536: (2, 6, 1024),
    262144: (1, 4, 2048),
    1_000_000: (1, 4, 8192),
}


def sparse_sweep(csv: Csv, ks, jax_repeat: int = 2):
    """Sparse-format K-sweep; returns the cell records ``--out`` writes."""
    cells = []
    for k in ks:
        lo, hi, b = _SPARSE_SWEEP[k]
        pop = _edge_pop(k, lo, hi, seed=k % 7919)
        repeat = 1 if k > 262_144 else jax_repeat

        plans = {}

        def build(seed=1):
            plans["p"] = ugs_plan(pop, b, seed=seed, backend="jax",
                                  plan_format="sparse")

        us = time_us(build, repeat=repeat, warmup=1, best=True)
        plan = plans["p"]
        t_steps = plan.num_steps
        dense_bytes = t_steps * k * 8
        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        cell = {
            "method": "ugs", "backend": "jax", "plan_format": "sparse",
            "clients": k, "global_batch": b,
            "total_samples": int(pop.total_size), "steps": int(t_steps),
            "nnz": int(plan.nnz), "best_of": repeat,
            "plan_seconds": round(us / 1e6, 3),
            "plan_bytes": int(plan.plan_nbytes),
            "dense_plan_bytes": int(dense_bytes),
            "bytes_per_client": round(plan.plan_nbytes / k, 2),
            "dense_ratio": round(dense_bytes / plan.plan_nbytes, 1),
            "rss_peak_kb": int(rss_kb),
        }
        cells.append(cell)
        csv.add(f"fig3_sparse_sweep[ugs,K={k},B={b}]", us,
                f"seconds={us/1e6:.2f};plan_mb={plan.plan_nbytes/2**20:.1f};"
                f"dense_mb={dense_bytes/2**20:.1f};"
                f"rss_peak_mb={rss_kb/1024:.0f}")
    return cells


def run(csv: Csv, quick: bool = False):
    # ---- paper fidelity: LDS overhead vs UGS at the paper's scale --------
    ks = [16, 128] if quick else [16, 32, 64, 128, 256]
    b = 128
    for k in ks:
        pop = _pop(k, seed=k + 7)
        pop.delays[:] = assign_delays(k, 0.2, 100, 500, seed=k)
        us_ugs = time_us(lambda: ugs_plan(pop, b, seed=0), repeat=3,
                         best=True)
        csv.add(f"fig3_sampling_time[ugs,K={k}]", us_ugs,
                f"seconds={us_ugs/1e6:.3f}")
        for delta in ([1.5] if quick else [0.5, 1.5]):
            us_lds = time_us(lambda: lds_plan(pop, b, delta=delta, seed=0),
                             repeat=3, best=True)
            csv.add(f"fig3_sampling_time[lds{delta},K={k}]", us_lds,
                    f"seconds={us_lds/1e6:.3f};overhead_x={us_lds/us_ugs:.2f}")

    # ---- planner-engine K-sweep: numpy reference vs jax backend ----------
    # UGS: fixed B = 128 (paper geometry); per-client ~16-24 samples. Both
    # backends materialize the dense (T, K) plan, which bounds the UGS
    # ratio; reported for the scaling curve. The 65536 cells live in
    # --full: their dense (T, K) plans run to gigabytes, too heavy for the
    # CI-sized quick pass.
    ugs_ks = [1024, 8192, 16384] if quick else [1024, 8192, 32768, 65536]
    for k in ugs_ks:
        pop = _sweep_pop(k, per=16, seed=k)
        _sweep_cell(csv, "ugs", k,
                    lambda s: ugs_plan(pop, 128, seed=s),
                    lambda s: ugs_plan(pop, 128, seed=s, backend="jax"))

    # LDS: B = 256; planning cost is dominated by the MAP-EM re-estimation
    # after every RemoveComponent, which the engine keeps on-device.
    lds_ks = [4096, 16384] if quick else [4096, 16384, 65536]
    for k in lds_ks:
        pop = _sweep_pop(k, per=20, seed=k + 1)
        _sweep_cell(csv, "lds", k,
                    lambda s: lds_plan(pop, 256, seed=s),
                    lambda s: lds_plan(pop, 256, seed=s, backend="jax"))

    # ---- sparse-format scaling (summary cells; the full K = 1e6 sweep
    # runs via this module's main) -----------------------------------------
    sparse_sweep(csv, [4096] if quick else [4096, 65536])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI: small-K cells only")
    ap.add_argument("--full", action="store_true",
                    help="paper-fidelity grids + the K = 1e6 sparse sweep")
    ap.add_argument("--out", default=None,
                    help="write the sparse-sweep JSON artifact here")
    args = ap.parse_args()

    csv = Csv()
    csv.header()
    if args.smoke:
        ks = [4096, 65536]
    else:
        ks = [4096, 65536, 262144, 1_000_000]
    cells = sparse_sweep(csv, ks)
    if not args.smoke:
        run(csv, quick=not args.full)

    if args.out:
        result = {
            "bench": "fig3_plan_scaling",
            "timing": "best-of-N steady state; jit compile excluded by an "
                      "untimed warmup call",
            "note": "sparse plans store per-step active-client segments "
                    "(O(T*B) memory); dense_ratio = dense (T, K) matrix "
                    "bytes / sparse plan bytes. rss_peak_kb is the "
                    "process high-water mark (monotone across cells).",
            "sweeps": cells,
        }
        pathlib.Path(args.out).write_text(json.dumps(result, indent=2)
                                          + "\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
