"""Benchmark runner: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import os
import sys


def main() -> None:
    from . import fault_injection, weight_distribution, wot_admm_compare, \
        wot_training

    print("name,us_per_call,derived")
    weight_distribution.main()
    wot_training.main()
    fault_injection.main([])  # explicit argv: don't inherit run.py's
    wot_admm_compare.main()

    # roofline rows if a dry-run result file exists
    for path in ("results/dryrun_16x16.jsonl", "results/dryrun_2x16x16.jsonl"):
        if os.path.exists(path):
            from . import roofline
            sys.argv = ["roofline", path]
            roofline.main()


if __name__ == "__main__":
    main()
