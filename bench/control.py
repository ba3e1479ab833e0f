"""Read the check's two ends on the chip, at a cell's own size.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed: one run of the cell as the benchmark runs it, whose numbers
are the program's (the lower reading), and the same sampled requests
answered by the reference at the control's precision in the program's
place (the upper reading).  Not part of a benchmark run: the limits in
``configs/<config>.json`` are set from these readings.  One process, so
that only the first seed compiles.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        print("CONTROL " + json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["check"].items()},
            "control": res["control"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
