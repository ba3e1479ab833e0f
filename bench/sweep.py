"""Find the highest rate a cell's traffic sustains: one set-up, then one
window per offered rate, in rising order.

    python bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 15 --seed <n>

Not part of a benchmark run: it sets the fixed rate and deadline a traffic
file holds.  Each rate prints one line: offered and answered requests, the
tails from scheduled arrival, mean batch size, how long the queue took to
drain past the window's close, and the granted eps.  A rate past the knee
shows a drain that grows with the window.  It stops after the first rate
whose drain exceeds ``--stop-drain`` seconds.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, serving  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--stop-drain", type=float, default=10.0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    c = run.load_cell(args.workload)
    devices = run.check_devices(c.cell["chips"])
    with run.jax.default_matmul_precision(c.cfg["matmul_precision"]):
        st = run.set_up(c, devices, args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(c.mix, rate_per_s=rate)
            if args.deadline_ms is not None:
                mix["deadline_ms"] = args.deadline_ms
            w = run.serve_window(c, st, mix, args.seed, args.seconds, False)
            drain = max((o.final_at for o in w.outcomes if o.answered),
                        default=0.0) - (w.outcomes[0].due - 0.0)
            sizes = [b.n for b in w.batches]
            line = {
                "rate": rate, "due": len(w.outcomes), "answered": w.answered,
                "mean_batch": sum(sizes) / max(len(sizes), 1),
                "granted": serving.result_lines(w.outcomes)["granted_eps"],
                "escalated": serving.result_lines(w.outcomes)["escalated"],
                **{k: round(v, 3) for k, v in w.e2e.items()},
                "last_answer_after_first_due_s": round(drain, 3),
            }
            print("SWEEP " + json.dumps(line), flush=True)
            past_close = drain - args.seconds
            if past_close > args.stop_drain:
                break
    return 0


if __name__ == "__main__":
    sys.exit(main())
