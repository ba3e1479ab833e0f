"""The yardstick's own arithmetic, on the CPU: trace reduction, tails,
the arrival generator, kernels' algorithmic counts and the spec's shape."""
import json
import math
import re
import statistics
import types
from pathlib import Path

import numpy as np
import pytest

from bench import devtrace, layers, registry, stats, traffic
from bench.run import end_to_end
from bench.serving import Outcome

FIXTURE = Path(__file__).parent / "fixtures" / "knn_batch_trace.json"


def _ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops"):
    return devtrace.Event(plane, line, name, float(start), float(dur))


def _host(name, start, dur):
    return _ev(name, start, dur, plane="/host:CPU", line="python3")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_reduce_busy_union_gaps_and_labels():
    events = [
        _host(devtrace.WINDOW, 0, 100),
        _host("host.step", 0, 60),
        _host("host.unpack", 18, 10),
        _host("host.idle", 60, 40),
        _ev("%a.1 = f32[4] custom-call(x)", 0, 10),
        _ev("%b.2 = f32[4] fusion(x)", 5, 15),        # overlaps a: union
        _ev("%a.1 = f32[4] custom-call(x)", 30, 10),
        _ev("%c.3 = f32[4] fusion(x)", 95, 20),       # runs past the window
        _ev("%d.4 = f32[4] fusion(x)", 200, 5),       # outside the window
    ]
    r = devtrace.reduce(events)
    assert r.window_s == pytest.approx(100e-9)
    # busy: [0, 20) + [30, 40) + [95, 100) = 35 ns
    assert r.busy_s == pytest.approx(35e-9)
    gaps = dict(r.idle_gaps)
    # [20, 30): middle 25 lies in host.step and host.unpack -> innermost
    assert gaps["host.unpack"] == pytest.approx(10e-9)
    # [40, 95): middle 67.5 lies in host.idle only
    assert gaps["host.idle"] == pytest.approx(55e-9)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.kernel_s([r"%a\.1 = "]) == pytest.approx(20e-9)
    top = dict(r.top_ops)
    assert top["%a.1 = f32[4] custom-call(x)"] == pytest.approx(20e-9)
    assert top["%c.3 = f32[4] fusion(x)"] == pytest.approx(5e-9)  # clipped


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        devtrace.reduce([_ev("%a = f32[] fusion()", 0, 1)])


def _recorded():
    data = json.loads(FIXTURE.read_text())
    return [devtrace.Event(e["plane"], e["line"], e["name"], e["start_ns"],
                           e["dur_ns"]) for e in data["events"]]


def test_reduce_recorded_trace_matches_a_brute_force_count():
    events = _recorded()
    r = devtrace.reduce(events)
    lo, hi = devtrace.window(events)
    # Busy time by sampling the window on a 1 us grid.
    grid = np.arange(lo, hi, 1000.0) + 500.0
    covered = np.zeros(grid.shape, bool)
    for e in devtrace.device_ops(events)["/device:TPU:0"]:
        covered |= (grid >= e.start_ns) & (grid < e.end_ns)
    assert r.busy_s == pytest.approx(covered.mean() * (hi - lo) / 1e9,
                                     rel=1e-3)
    assert 0.0 < r.busy_s <= r.window_s
    # every idle nanosecond is attributed to some label
    assert sum(t for _, t in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-9)
    # loop ops are left out of the top ops: their time is their body's
    assert not any(" while(" in name for name, _ in r.top_ops)


@pytest.mark.parametrize("kernel,calls", [
    ("distance_topk", 1), ("knn_distance", 1), ("refine_distances", 90),
])
def test_kernel_patterns_find_the_recorded_kernels(kernel, calls):
    """One served refined kNN batch: one stage-1 top-k, one stage-1 pass
    inside the refined map, and the refine row walk in 90 chunks."""
    ops = devtrace.device_ops(_recorded())["/device:TPU:0"]
    match = registry.kernel(kernel).MATCH
    assert sum(devtrace.matches(e, match) for e in ops) == calls


def test_read_profile_of_a_host_trace(tmp_path):
    """The parser reads the harness's own annotations from a real profile;
    a trace with no device plane has no busy time."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    jax.block_until_ready(f(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            with jax.profiler.TraceAnnotation("host.step"):
                jax.block_until_ready(f(x))
    finally:
        jax.profiler.stop_trace()
    events = devtrace.read_profile(str(tmp_path))
    names = {e.name for e in events}
    assert {devtrace.WINDOW, "host.step"} <= names
    r = devtrace.reduce(events)
    assert r.window_s > 0 and r.busy_s == 0.0 and r.ops == []
    assert r.idle_gaps == []  # no device: nothing was idle


# ---------------------------------------------------------------------------
# tails over all requests
# ---------------------------------------------------------------------------

def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([5.0], 90) == 5.0
    assert math.isnan(stats.percentile([], 90))


def test_failed_requests_read_above_every_answer():
    lat = stats.latencies_with_failures([1.0, 2.0, 3.0], 2, fail_value=2.5)
    assert lat[-2:] == [3.001, 3.001]
    assert stats.percentile(lat, 90) == pytest.approx(3.001)


def _outcome(due, s1, fin, **kw):
    o = Outcome(due=due, submitted=due, stage1_at=s1, final_at=fin, **kw)
    return o


def test_end_to_end_counts_unanswered_and_lower_grants():
    nan = math.nan
    outcomes = [
        _outcome(0.0, 0.1, 0.2, eps=0.08, refined=True) for _ in range(8)
    ] + [
        _outcome(0.0, 0.1, 0.3, eps=0.04, refined=True),    # lower grant
        _outcome(0.0, nan, nan),                            # never answered
    ]
    m = end_to_end(outcomes, t_end=10.0, eps_max=0.08)
    # p90 of 10 requests is the 9th: the lower-granted one at 300 ms
    assert m["answer_p90_ms"] == pytest.approx(300.0)
    assert m["answer_p50_ms"] == pytest.approx(200.0)
    assert m["stage1_p90_ms"] == pytest.approx(100.0)
    assert m["full_answer_share"] == pytest.approx(0.8)
    # one more failure and the p90 lands on a failure: the give-up time
    outcomes[0] = _outcome(0.0, nan, nan)
    m = end_to_end(outcomes, t_end=10.0, eps_max=0.08)
    assert m["answer_p90_ms"] == pytest.approx(10_000.0)


def test_end_to_end_stage1_mix_counts_met_bounds():
    outcomes = [_outcome(0.0, 0.01, 0.01, eps=0.08, skipped=True,
                         accuracy_met=True) for _ in range(3)]
    outcomes.append(_outcome(0.0, 0.01, 0.05, eps=0.08, refined=True))
    m = end_to_end(outcomes, t_end=1.0, eps_max=0.08)
    assert m["full_answer_share"] == 1.0


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 40.0]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / q2)
    assert stats.spread_without_farthest(vals) < stats.spread(vals)


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------

def test_schedule_same_arrivals_every_seed_queries_by_seed():
    mix = {"rate_per_s": 8.0}
    a, ia = traffic.schedule(mix, 2**31 + 5, 30.0, pool_size=100)
    b, ib = traffic.schedule(mix, 7, 30.0, pool_size=100)
    assert len(a) == 240
    assert np.all(np.diff(a) > 0) and a[-1] < 30.0 and a[0] >= 0.0
    np.testing.assert_array_equal(a, b)          # one arrival path
    assert not np.array_equal(ia, ib)            # queries by the seed
    assert ia.min() >= 0 and ia.max() < 100
    # the gaps are the exponential quantiles, scaled to fill the window
    gaps = np.sort(np.diff(a, prepend=0.0))
    want = np.sort(traffic.unit_gaps(240))
    np.testing.assert_allclose(gaps / gaps.sum(), want / want.sum())
    with pytest.raises(ValueError):
        traffic.schedule(dict(mix, arrivals="uniform"), 7, 30.0, 100)


# ---------------------------------------------------------------------------
# algorithmic counts at the cells' shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel,shape,flops,nbytes", [
    ("distance_topk", dict(q=4, n=131072, d=217, k=6),
     2 * 4 * 131072 * 217 + 2 * 131072 * 217 + 3 * 4 * 131072,
     4 * (131072 * 217 + 2 * 131072 + 4 * 217 + 2 * 4 * 6)),
    ("knn_distance", dict(q=4, n=131072, d=217),
     2 * 4 * 131072 * 217 + 2 * 131072 * 217 + 3 * 4 * 131072,
     4 * (131072 * 217 + 4 * 217 + 4 * 131072)),
    ("refine_distances", dict(q=4, b=184000, d=217),
     3 * 4 * 184000 * 217, 4 * (4 * 184000 * 217 + 3 * 4 * 184000 + 4 * 217)),
    ("cf_weights", dict(q=4, u=256, i=3706),
     6 * 4 * 256 * 3706, 4 * (2 * 256 * 3706 + 2 * 4 * 3706 + 4 * 256)),
    ("cf_refine", dict(q=4, b=1933, i=3706),
     12 * 4 * 1933 * 3706,
     4 * (2 * 4 * 1933 * 3706 + 3 * 4 * 1933 + 4 * 4 * 3706)),
])
def test_kernel_work_at_the_cells_shapes(kernel, shape, flops, nbytes):
    f, b = registry.kernel(kernel).work(**shape)
    assert f == flops and b == nbytes


def _ctx(app_name, batches, kernel_s, window_s=10.0):
    spec = registry.spec()
    cfg_name = {"knn": "knn-mfeat2.3m", "cf": "cf-ml1m"}[app_name]
    peaks = registry.peaks()["devices"]["TPU v5 lite"]
    device = types.SimpleNamespace(kernel_s=lambda _m: kernel_s,
                                   window_s=window_s, ops=[1])
    return types.SimpleNamespace(
        cfg=registry.config(spec, cfg_name), app=registry.app(app_name),
        batches=batches, device=device, peaks=peaks, kernel=registry.kernel)


def test_roofline_share_sums_every_batch_and_stage():
    b = types.SimpleNamespace(n=4, refine_budget=184000, spans=None)
    ctx = _ctx("knn", [b, b], kernel_s=0.5)
    _, nbytes = registry.kernel("refine_distances").work(q=4, b=184000, d=217)
    want = 100.0 * 2 * nbytes / 8.19e11 / 0.5
    assert layers.kernel_roofline(ctx, "refine_distances") == \
        pytest.approx(want)
    # a stage-1-only batch runs no refinement: nothing to read
    b0 = types.SimpleNamespace(n=4, refine_budget=0, spans=None)
    assert layers.kernel_roofline(_ctx("knn", [b0], 0.5),
                                  "refine_distances") is None


def test_map_share_counts_stage2_only_where_granted():
    cfg = registry.config(registry.spec(), "cf-ml1m")
    app = registry.app("cf")
    b1 = types.SimpleNamespace(n=3, refine_budget=1933, spans=None)
    b0 = types.SimpleNamespace(n=3, refine_budget=0, spans=None)
    ctx = _ctx("cf", [b1, b0], kernel_s=1.0, window_s=2.0)
    w1, w0 = app.map_work(cfg, 3, 1933), app.map_work(cfg, 3, 0)
    need = sum(layers.min_time_s(ctx, *w) for w in
               (w1["stage1"], w1["stage2"], w0["stage1"]))
    assert layers.map_share(ctx, ("stage1", "stage2")) == \
        pytest.approx(100.0 * need / 2.0)


# ---------------------------------------------------------------------------
# the spec: every name resolves to its files, within the contract's limits
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_spec_names_resolve_to_files():
    spec = registry.spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    assert "setup_s" in e2e
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        cfg = registry.config(spec, c["name"])
        registry.app(cfg["app"])
        registry.reference(cfg["app"])
        assert all(isinstance(v, (int, float))
                   for v in cfg["check"]["limits"].values())
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        mix = registry.traffic(w["traffic"])
        assert mix["rate_per_s"] > 0 and mix["deadline_ms"] > 0
    for m in spec["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert callable(registry.metric(m["name"]).read)
    for m in spec["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
