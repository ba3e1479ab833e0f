"""Run one cell of ``BENCHMARK.json`` once, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the data on the device from the seed, builds the servable
through ``repro`` (aggregates, ``Server.calibrate`` once where the
configuration says so, ``prewarm`` of the one pad size at refine budgets
{0, eps_max}) and drives a warm-up step of every batch size; ``setup_s``
is all of it.  The window then offers the
cell's traffic open-loop for ``--seconds`` through ``Server.submit`` /
``Server.step`` (``bench/serving.py``) and waits up to a minute past its
close for the answers.  Afterwards a seeded sample of the answers is
compared with the plain reference (``bench/reference/<app>.py``); the
numbers compared are printed beside their limits as the last lines of
standard error and under ``check`` in the result.

``--trace 1`` profiles the window and reports the per-layer metrics
instead of the end-to-end ones.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (``breakdown`` with ``--trace 1``) and ``check``.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import devtrace, registry, serving, stats, traffic  # noqa: E402

# Answers due in the window are waited for this long past its close.
DRAIN_S = 60.0
# JAX reports this for every program it compiles or loads from the
# persistent cache, and the second one for each cache miss.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class ChipMissing(RuntimeError):
    pass


def check_devices(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise ChipMissing(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices[:chips]


def key_from_seed(seed: int, stream: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    state = np.random.SeedSequence([int(seed) % 2**128, stream])
    return jnp.asarray(state.generate_state(2, np.uint32), dtype=jnp.uint32)


class CompileCounter:
    """Counts programs compiled or loaded (``count``) and persistent-cache
    misses (``misses``)."""

    def __init__(self):
        self.count = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.misses += 1


def cache_entries() -> dict[str, int]:
    """The persistent cache's entries (``<program>-<key>-cache``) and their
    sizes in bytes."""
    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return {}
    return {e.name: e.stat().st_size for e in os.scandir(d)
            if e.name.endswith("-cache")}


def new_entries(before: dict[str, int]) -> str:
    """The programs written to the persistent cache since ``before``."""
    new = sorted((size, name) for name, size in cache_entries().items()
                 if name not in before)
    return ", ".join(f"{name.removesuffix('-cache').rsplit('-', 1)[0]} "
                     f"({size} bytes)" for size, name in new) or "none"


class GcPauses:
    """The Python collector's pauses while it is watched (count, longest
    s), so that a stall of the load loop can be told from a collection."""

    def __init__(self):
        self.count = 0
        self.longest_s = 0.0
        self._t0 = 0.0

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t0)


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _fmt(x) -> str:
    return f"{x:.6g}" if isinstance(x, float) else str(x)


def end_to_end(outcomes, t_end, eps_max: float) -> dict:
    """The end-to-end metrics over every request due in the window.

    ``full_answer_share``: requests whose final answer is refined at the
    mix's ``eps_max`` (directly, or by the re-execution of an escalated
    request, which does that work too), or, where the mix skips stage 2,
    whose stage-1 bound met the request's ``max_error``.
    """
    n = len(outcomes)
    s1 = [(o.stage1_at - o.due) * 1e3 for o in outcomes
          if not math.isnan(o.stage1_at)]
    fin = [(o.final_at - o.due) * 1e3 for o in outcomes if o.answered]
    # A request with no answer reads the time the run gave up on it.
    give_up = max([(t_end - o.due) * 1e3 for o in outcomes], default=0.0)
    s1_all = stats.latencies_with_failures(s1, n - len(s1), give_up)
    fin_all = stats.latencies_with_failures(fin, n - len(fin), give_up)
    full = sum(o.answered and (
        (o.skipped and o.accuracy_met is True)
        or (o.refined and abs(o.eps - eps_max) < 1e-12)) for o in outcomes)
    return {
        "stage1_p90_ms": stats.percentile(s1_all, 90),
        "answer_p90_ms": stats.percentile(fin_all, 90),
        "answer_p50_ms": stats.percentile(fin_all, 50),
        "full_answer_share": full / n if n else 0.0,
    }


def _samples(app, outcomes, batches, cfg, seed):
    """Seeded sample of answered requests; each gives its stage-1 answer
    and, if refined, its refined answer (an escalated request's from its
    re-execution), each with the refinement budget it ran at."""
    answered = [i for i, o in enumerate(outcomes)
                if o.answered and o.batch >= 0]
    rng = traffic.rng_for(seed, 3)
    take = min(cfg["check"]["sample_requests"], len(answered))
    picked = sorted(rng.choice(answered, size=take, replace=False)) if take \
        else []
    rows = []
    for i in picked:
        o = outcomes[i]
        answers = [(0, batches[o.batch].outputs[0], o.row)]
        if o.refined:
            fb = batches[o.final_batch]
            answers.append((fb.refine_budget, fb.outputs[1], o.final_row))
        for budget, out, row_index in answers:
            row = app.answer_row(out, row_index)
            row.update(request=i, budget=budget)
            rows.append(row)
    return rows


def load_cell(workload: str, *, root: Path = ROOT,
              config_overrides: dict | None = None,
              traffic_overrides: dict | None = None):
    """The cell's spec entry, configuration, traffic, app and reference."""
    spec = registry.spec(root)
    cell = registry.cell(spec, workload)
    cfg = registry.config(spec, cell["config"], root)
    cfg.update(config_overrides or {})
    mix = registry.traffic(cell["traffic"])
    mix.update(traffic_overrides or {})
    return types.SimpleNamespace(
        spec=spec, cell=cell, cfg=cfg, mix=mix, app=registry.app(cfg["app"]),
        ref=registry.reference(cfg["app"]),
    )


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    root: Path = ROOT, config_overrides: dict | None = None,
    traffic_overrides: dict | None = None, require_tpu: bool = True,
    fault=None, control: bool = False,
) -> dict:
    """Run one cell once and return the result object.

    ``config_overrides``/``traffic_overrides``/``require_tpu``/``fault``
    let the tests drive a tiny copy of a cell on the CPU.  ``control``
    also reads the control: the reference at the control's precision put
    in the program's place (under ``control`` in the result).
    """
    c = load_cell(workload, root=root, config_overrides=config_overrides,
                  traffic_overrides=traffic_overrides)
    devices = (check_devices(c.cell["chips"]) if require_tpu
               else jax.devices()[:c.cell["chips"]])
    with jax.default_matmul_precision(c.cfg["matmul_precision"]):
        st = set_up(c, devices, seed, fault)
        w = serve_window(c, st, c.mix, seed, seconds, trace)
        return finish(c, st, w, seed, trace, control)


def set_up(c, devices, seed: int, fault=None):
    """Data, servable, server, aggregates, calibration, prewarm, warm-up
    steps: everything ``setup_s`` covers."""
    from repro.core.budget import BudgetPolicy
    from repro.serve.deadline import DeadlineController
    from repro.serve.scheduler import ContinuousBatcher
    from repro.serve.server import Server

    cfg, mix, app = c.cfg, c.mix, c.app
    st = types.SimpleNamespace(devices=devices, compiles=CompileCounter(),
                               phases={})
    cached_before = cache_entries()
    eps_max = float(mix["eps_max"])
    pad = int(cfg["max_batch"])
    t_setup = time.perf_counter()

    def phase(name, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        st.phases[name] = time.perf_counter() - t0
        return out

    st.data = phase("data", lambda: app.make_data(cfg, key_from_seed(seed, 0)))
    st.lsh_key = key_from_seed(seed, 1)
    st.servable = phase("servable",
                        lambda: app.make_servable(cfg, st.data, st.lsh_key))
    if fault is not None:
        fault(st.servable)
    st.pool = app.payloads(st.data)
    st.clock = serving.MarkingClock()
    policy = BudgetPolicy(
        compression_ratio=float(cfg["compression_ratio"]), eps_max=eps_max,
        degrade_floor=float(cfg["degrade_floor"]),
    )
    st.server = server = Server(
        [st.servable], controller=DeadlineController(policy),
        batcher=ContinuousBatcher(max_batch=pad, pad_sizes=(pad,)),
        clock=st.clock,
    )
    kind = st.kind = st.servable.name
    st.prepared = phase("aggregates", lambda: server.cache.get_or_build(
        st.servable, policy.compression_ratio)[0])
    st.realized = app.realized(st.prepared)
    if cfg["calibrate"]:
        phase("calibrate", lambda: server.calibrate(kind, batch=pad))
    phase("prewarm", lambda: server.prewarm(kind, batch=pad,
                                            eps_values=[eps_max]))

    def warm_steps():
        # Every batch size the window can form, through the whole step.
        deadline_s = float(mix["deadline_ms"]) / 1e3
        for n in range(1, pad + 1):
            for i in range(n):
                server.submit(kind, app.payload(st.pool, i), deadline_s,
                              max_error=mix.get("max_error"))
            server.drain()
            st.servable.take_recorded()

    phase("warm_steps", warm_steps)
    server.reset_metrics()
    # What set-up made lives to the end: the collector need not walk it
    # again on every full collection inside the window.
    gc.collect()
    gc.freeze()
    st.setup_s = time.perf_counter() - t_setup
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in st.phases.items())
        + f"; total {st.setup_s:.3f} s; programs compiled or loaded "
        f"{st.compiles.count}, of which missed the persistent cache "
        f"{st.compiles.misses}")
    log(f"programs written to the persistent cache: "
        f"{new_entries(cached_before)}")
    log(f"cost model: {server.controller.models.get(kind)}")
    if st.realized["aggregates"] != cfg["n_aggregates"]:
        log(f"aggregates: program built {st.realized['aggregates']}, the "
            f"configuration states {cfg['n_aggregates']}")
    log(f"aggregates: {st.realized}")
    st.mem_setup = _memory_peak(devices)
    return st


def serve_window(c, st, mix, seed: int, seconds: float, trace: bool):
    """Offer ``mix`` open-loop for ``seconds`` and read what came back."""
    from repro.obs.trace import Tracer

    app, server, servable = c.app, st.server, st.servable
    eps_max = float(mix["eps_max"])
    max_error = mix.get("max_error")
    pool_size = len(st.pool[0]) if isinstance(st.pool, tuple) \
        else len(st.pool)
    w = types.SimpleNamespace(reduced=None, per_layer={})
    due, w.pool_idx = traffic.schedule(mix, seed, seconds, pool_size)
    payloads = [app.payload(st.pool, int(j)) for j in w.pool_idx]
    if trace:
        server.tracer = Tracer()
        servable.annotate = True
    profile_dir = tempfile.mkdtemp(prefix="bench_profile_") if trace else None
    compiles_before = st.compiles.count
    if trace:
        jax.profiler.start_trace(profile_dir)
    window = (jax.profiler.TraceAnnotation(devtrace.WINDOW) if trace
              else contextlib.nullcontext())
    with window, GcPauses() as gc_pauses:
        (w.outcomes, w.batches, _t0, t_close, t_end,
         late, pauses) = serving.drive(
            server, st.kind, servable, due, payloads,
            deadline_s=float(mix["deadline_ms"]) / 1e3, max_error=max_error,
            seconds=seconds, drain_s=DRAIN_S, clock=st.clock,
            annotate=trace,
        )
    if trace:
        jax.profiler.stop_trace()
        servable.annotate = False
    w.window_compiles = st.compiles.count - compiles_before
    w.memory_peak = _memory_peak(st.devices)

    n = len(w.outcomes)
    w.answered = sum(o.answered for o in w.outcomes)
    w.e2e = end_to_end(w.outcomes, t_end, eps_max)
    w.e2e["setup_s"] = st.setup_s
    counts = serving.result_lines(w.outcomes)
    log(f"window: {seconds:g} s at {mix['rate_per_s']} req/s, deadline "
        f"{mix['deadline_ms']} ms, eps_max {eps_max}, max_error {max_error};"
        f" {n} due, {w.answered} answered, {len(w.batches)} batches; "
        f"drained {t_end - t_close:.3f} s past the close")
    log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms" if len(late) else "generator: none")
    inside = ", ".join(f"{k} {v * 1e3:.3f} ms"
                       for k, v in pauses["step_phases"].items())
    log(f"host pauses: longest step {pauses['step_s'] * 1e3:.3f} ms at "
        f"{pauses['step_at_s']:.3f} s ({inside}; the rest in Server and "
        f"waiting for the device), longest overshoot of a sleep "
        f"{pauses['oversleep_s'] * 1e3:.3f} ms; "
        f"{gc_pauses.count} garbage collections, longest "
        f"{gc_pauses.longest_s * 1e3:.3f} ms")
    log(f"granted eps: {counts['granted_eps']}; refined {counts['refined']}, "
        f"escalated {counts['escalated']}, skipped {counts['skipped']}")
    log(f"programs compiled or loaded inside the window: "
        f"{w.window_compiles}")
    log(f"peak HBM: {w.memory_peak} bytes (after set-up {st.mem_setup})")
    log("end to end: " + ", ".join(f"{k} {_fmt(v)}"
                                   for k, v in w.e2e.items()))
    if trace:
        w.reduced = devtrace.reduce(devtrace.read_profile(profile_dir))
        shutil.rmtree(profile_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            cfg=c.cfg, app=app, batches=w.batches, device=w.reduced,
            peaks=peaks_for(st.devices[0].device_kind),
            kernel=registry.kernel,
        )
        for m in c.spec["per_layer"]:
            if "workloads" in m and c.cell["name"] not in m["workloads"]:
                continue
            value = registry.metric(m["name"]).read(ctx)
            if value is not None:
                w.per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
        log("per layer: " + ", ".join(
            f"{k} {_fmt(v['value'])}" for k, v in w.per_layer.items()))
    return w


def finish(c, st, w, seed: int, trace: bool, control: bool) -> dict:
    """Check a seeded sample against the reference; assemble the result."""
    cfg = c.cfg
    rows = _samples(c.app, w.outcomes, w.batches, cfg, seed)
    # The program's state goes before the reference runs, so that the
    # reference neither shares the chip's memory with it nor sets the peak.
    st.server = st.servable = st.prepared = None
    w.batches = None
    gc.unfreeze()
    gc.collect()
    limits = cfg["check"]["limits"]
    numbers = check(c, st, w.pool_idx, rows)
    correct = bool(rows) and all(numbers[k] <= limits[k] for k in limits)

    if trace:
        metrics = w.per_layer
    else:
        metrics = {
            m["name"]: {"value": w.e2e[m["name"]], "unit": m["unit"]}
            for m in c.spec["end_to_end"]
            if "workloads" not in m or c.cell["name"] in m["workloads"]
        }
    dev = st.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(st.devices), "memory_peak_bytes": w.memory_peak}
    n = len(w.outcomes)
    result = {"correct": correct, "attempted": n, "failed": n - w.answered,
              "metrics": metrics, "device": device}
    if w.reduced is not None:
        device["busy_s"] = w.reduced.busy_s
        device["window_s"] = w.reduced.window_s
        result["breakdown"] = {"device_ops": w.reduced.top_ops,
                               "idle_gaps": w.reduced.idle_gaps}
    if control:
        result["control"] = check(c, st, w.pool_idx, rows, control=True)
        log("control: " + ", ".join(f"{k} {_fmt(v)}"
                                    for k, v in result["control"].items()))
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    log(f"check: {len(rows)} answers of "
        f"{len({r['request'] for r in rows})} sampled requests")
    for k in limits:
        log(f"check {k}: {_fmt(numbers[k])} (limit {_fmt(limits[k])})")
    return result


def check(c, st, pool_idx, rows, *, control=False) -> dict:
    """The numbers compared: the sampled answers against the reference
    (or, for the control, the reference at the control's precision)."""
    queries = [c.app.payload(st.pool, int(pool_idx[r["request"]]))
               for r in rows]
    budgets = [r["budget"] for r in rows]
    return c.ref.check(c.cfg, st.data, st.lsh_key, queries, budgets, rows,
                       control=control)


def peaks_for(device_kind: str) -> dict:
    table = registry.peaks()["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_persistent_cache

    log(f"compile cache: {use_persistent_cache()}")
    # Cache every program, however quick to compile, so that only the first
    # run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except ChipMissing as e:
        log(str(e))
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
