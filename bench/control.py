"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload ids-v1.mixed-small \\
        --seeds 101,102,103 --seconds 10

For each seed, in one process (so compiled programs are shared), it runs
the cell as ``run.py`` does, at its own size and load, and judges the same
served packets twice: the program's answers against the reference, and the
control's (the reference with features carried at 4 bits, the precision
below the configuration's 8-bit features) against the reference.  One JSON
line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import load_cell, require_chips, run_cell, use_compile_cache  # noqa

CONTROL_BITS = 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devices = require_chips(cell["chips"])
    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, devices,
                       control_bits=CONTROL_BITS)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"],
            **{k: c["value"] for k, c in out["checks"].items()},
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
