"""Benchmark driver: one benchmark per paper figure + serving/store benches
+ the roofline table.

Prints ``name,us_per_call,derived`` CSV lines (the contract for
bench_output.txt) and finishes with ONE combined ``BENCH`` json line
aggregating every sub-benchmark's summary, so the perf trajectory is
machine-readable from a single grep.

Result files (all optional):

  * ``--out PATH``            — write this run's combined dict as json (the
    "current" side of ``benchmarks/compare.py``; always overwritten — it is
    a run artifact, not a baseline);
  * ``--baselines DIR``       — write the combined dict to
    ``BENCH_combined.json`` plus one ``BENCH_<suite>.json`` per suite.
    Baselines are reference points: an existing file is REFUSED unless
    ``--update-baseline`` is passed, so a stray run can't silently move
    the bar the regression gate measures against;
  * ``--suites a,b,c``        — run only those suites (CI's compare step
    runs the serving/store/kernel trio twice without paying for the paper
    figures).

Failure contract for CI: the driver exits non-zero when any benchmark
raises *or* prints a ``BENCH_FAIL`` line (benchmarks use that to flag
internal guard failures — e.g. a reuse path slower than a rebuild — without
aborting the rest of the sweep).
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import traceback
from pathlib import Path


class _FailScanningTee(io.TextIOBase):
    """Pass-through stream that remembers whether BENCH_FAIL was printed."""

    def __init__(self, inner):
        self.inner = inner
        self.saw_fail = False

    def write(self, s: str) -> int:
        if "BENCH_FAIL" in s:
            self.saw_fail = True
        return self.inner.write(s)

    def flush(self) -> None:
        self.inner.flush()


def write_baselines(
    combined: dict, directory: Path, *, update: bool
) -> list[Path]:
    """Write combined + per-suite baseline jsons; refuse to clobber.

    Returns the written paths.  Raises ``SystemExit`` (non-zero) listing
    every existing baseline that would have been overwritten when
    ``update`` is False — the caller asked for new baselines while old
    ones exist, which is exactly the accident this guards against.
    """
    directory.mkdir(parents=True, exist_ok=True)
    targets = [(directory / "BENCH_combined.json", combined)]
    for suite, summary in combined.items():
        if suite == "obs":  # registry snapshot rides the combined file only
            continue
        targets.append((directory / f"BENCH_{suite}.json", {suite: summary}))
    if not update:
        existing = [str(p) for p, _ in targets if p.exists()]
        if existing:
            raise SystemExit(
                "refusing to overwrite committed baseline(s) without "
                "--update-baseline:\n  " + "\n  ".join(existing)
            )
    written = []
    for path, payload in targets:
        path.write_text(json.dumps(payload, indent=2) + "\n")
        written.append(path)
    return written


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="benchmark suite driver")
    ap.add_argument("--out", type=Path, default=None,
                    help="write the combined BENCH json here (run artifact)")
    ap.add_argument("--baselines", type=Path, default=None,
                    help="directory for BENCH_<suite>.json baseline files")
    ap.add_argument("--update-baseline", action="store_true",
                    help="allow overwriting existing baseline files")
    ap.add_argument("--suites", type=str, default=None,
                    help="comma-separated subset of suites to run")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    from benchmarks import (
        chaos_soak, decode_bench, error_bounds, fig4_breakdown, fig5_shuffle,
        fig6_time_reduction, fig7_accuracy, fig8_vs_sampling, fig9_k_sweep,
        kernel_bench, roofline, serve_latency, store_reuse,
    )

    modules = [fig4_breakdown, fig5_shuffle, fig6_time_reduction,
               fig7_accuracy, fig8_vs_sampling, fig9_k_sweep,
               kernel_bench, serve_latency, decode_bench, store_reuse,
               chaos_soak, error_bounds, roofline]
    if args.suites:
        wanted = {s.strip() for s in args.suites.split(",") if s.strip()}
        names = {m.__name__.rsplit(".", 1)[-1] for m in modules}
        unknown = wanted - names
        if unknown:
            raise SystemExit(
                f"unknown suite(s) {sorted(unknown)}; have {sorted(names)}"
            )
        modules = [
            m for m in modules
            if m.__name__.rsplit(".", 1)[-1] in wanted
        ]

    out = _FailScanningTee(sys.stdout)
    err = _FailScanningTee(sys.stderr)
    sys.stdout, sys.stderr = out, err
    ok = True
    combined: dict = {}
    try:
        for mod in modules:
            name = mod.__name__.rsplit(".", 1)[-1]
            try:
                summary = mod.run()
                if isinstance(summary, dict):
                    combined[name] = summary
            except Exception:  # keep the harness going, report at the end
                ok = False
                print(f"BENCH_FAIL,{name}", file=sys.stderr)
                traceback.print_exc()
    finally:
        sys.stdout, sys.stderr = out.inner, err.inner

    # Process-wide metrics registry (kernel-probe measured p50s, runtime
    # shard events) snapshots into the combined line: per-kernel measured
    # time and serving accuracy proxies travel with every perf data point.
    from repro.obs.metrics import default_registry

    combined["obs"] = default_registry().snapshot()
    print("BENCH " + json.dumps(combined))
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(combined, indent=2) + "\n")
    if args.baselines is not None:
        written = write_baselines(
            combined, args.baselines, update=args.update_baseline
        )
        print("baselines written: " + ", ".join(str(p) for p in written))
    if not ok or out.saw_fail or err.saw_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
