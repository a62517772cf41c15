"""Run the control of ``correct`` for one cell on several seeds.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

Prints, per seed, each number compared beside its limit and whether the
control came out correct (it must not)."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--per-client", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark.common.control import control_numbers

    for seed in (int(s) for s in args.seeds.split(",")):
        correct, compared = control_numbers(ROOT, args.workload, seed, args.per_client)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": correct,
                          "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
