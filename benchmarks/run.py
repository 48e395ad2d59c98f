# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. ``--full`` runs the paper-fidelity grids; default is the quick pass
# (same claims, smaller grids) suitable for CI.
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import Csv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-fidelity grids (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from benchmarks import (fig2_em_iters, fig3_sampling_time,
                            fig6_deviation, fig7_deviation_lds,
                            table2_accuracy, table3_lds_accuracy, table4_tpe)
    suites = {
        "fig6_deviation": fig6_deviation.run,
        "fig7_deviation_lds": fig7_deviation_lds.run,
        "table4_tpe": table4_tpe.run,
        "fig2_em_iters": fig2_em_iters.run,
        "fig3_sampling_time": fig3_sampling_time.run,
        "table2_accuracy": table2_accuracy.run,
        "table3_lds_accuracy": table3_lds_accuracy.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    csv = Csv()
    csv.header()
    failed = []
    for name, fn in suites.items():
        try:
            fn(csv, quick=quick)
        except Exception:  # noqa: BLE001 — report all suites
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
