"""Tests for repro.obs: tracing, the typed metrics registry, kernel probes,
the ServeMetrics reimplementation (bounded memory, API-compatible summary),
and the serving-path span tree end to end."""
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import cf as cf_lib
from repro.apps import knn as knn_lib
from repro.apps.knn import KNNServable
from repro.core import engine as engine_lib
from repro.core import lsh as lsh_lib
from repro.core.budget import BudgetPolicy, CostModel
from repro.kernels import ops as kernel_ops
from repro.obs.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, Reservoir,
    default_registry, percentile, validate_snapshot,
)
from repro.obs.probes import (
    KernelProbe, install_kernel_probe, uninstall_kernel_probe,
)
from repro.obs.trace import (
    NULL_TRACER, Tracer, current_tracer, use_tracer, validate_trace_jsonl,
)
from repro.serve import ContinuousBatcher, DeadlineController, Server
from repro.serve.metrics import ServeMetrics, slo_class
from repro.serve.request import Response

GOLDEN = Path(__file__).parent / "golden"


# ---------------------------------------------------------------------------
# percentile (satellite: pinned edge cases)
# ---------------------------------------------------------------------------

def test_percentile_edge_cases():
    assert math.isnan(percentile([], 50))
    assert percentile([7.0], 0) == 7.0
    assert percentile([7.0], 100) == 7.0
    xs = [3.0, 1.0, 2.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 3.0          # exactly max, no overshoot
    assert percentile(xs, 50) == 2.0
    assert percentile(xs, 150) == 3.0          # clamped
    assert percentile(xs, -10) == 1.0          # clamped


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=37).tolist()
    for p in (0, 1, 25, 50, 75, 99, 100):
        assert percentile(xs, p) == pytest.approx(
            float(np.percentile(xs, p)), rel=1e-12
        )


# ---------------------------------------------------------------------------
# series types
# ---------------------------------------------------------------------------

def test_counter_is_monotonic():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_moves_both_ways():
    g = Gauge()
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4.0


def test_histogram_cumulative_buckets():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(5.555)
    assert h.cumulative() == [(0.01, 1), (0.1, 2), (1.0, 3), (math.inf, 4)]


def test_reservoir_memory_stays_flat_with_exact_stats():
    r = Reservoir(capacity=64)
    for i in range(10_000):
        r.observe(float(i))
    assert len(r.samples) == 64          # bounded: the unbounded-list fix
    assert r.count == 10_000             # exact despite sampling
    assert r.sum == sum(range(10_000))
    assert r.min == 0.0 and r.max == 9_999.0
    # The retained sample is uniform-ish: p50 lands mid-range.
    assert 2_000 < r.percentile(50) < 8_000


def test_reservoir_is_deterministic():
    a, b = Reservoir(capacity=16), Reservoir(capacity=16)
    for i in range(1_000):
        a.observe(float(i))
        b.observe(float(i))
    assert a.samples == b.samples


# ---------------------------------------------------------------------------
# registry + families
# ---------------------------------------------------------------------------

def test_registry_declarations_are_idempotent():
    r = MetricsRegistry()
    a = r.counter("x_total", "help", labels=("kind",))
    b = r.counter("x_total", labels=("kind",))
    assert a is b


def test_registry_rejects_kind_and_label_mismatch():
    r = MetricsRegistry()
    r.counter("x_total", labels=("kind",))
    with pytest.raises(ValueError):
        r.gauge("x_total", labels=("kind",))      # kind mismatch
    with pytest.raises(ValueError):
        r.counter("x_total", labels=("other",))   # label mismatch


def test_labeled_series_and_label_validation():
    r = MetricsRegistry()
    fam = r.counter("req_total", labels=("kind", "slo"))
    fam.labels(kind="knn", slo="tight").inc(2)
    fam.labels(kind="cf", slo="tight").inc()
    assert fam.total() == 3
    assert len(list(fam.series())) == 2
    with pytest.raises(ValueError):
        fam.labels(kind="knn")                    # missing label
    with pytest.raises(ValueError):
        fam.inc()                                 # labeled family needs .labels


def test_labelless_family_proxies_series_api():
    r = MetricsRegistry()
    r.counter("a_total").inc(3)
    r.gauge("b").set(7)
    r.reservoir("c").observe(1.5)
    assert r.get("a_total").value == 3
    assert r.get("b").value == 7
    assert r.get("c").merged_stats()["count"] == 1


def test_registry_reset_zeroes_but_keeps_families():
    r = MetricsRegistry()
    fam = r.counter("x_total", labels=("kind",))
    fam.labels(kind="knn").inc(5)
    r.reset()
    assert fam.labels(kind="knn").value == 0
    assert r.get("x_total") is fam


# ---------------------------------------------------------------------------
# exports (satellite: golden-file schema stability)
# ---------------------------------------------------------------------------

def _golden_registry() -> MetricsRegistry:
    r = MetricsRegistry()
    c = r.counter("requests_total", "Requests by kind.", labels=("kind",))
    c.labels(kind="knn").inc(3)
    c.labels(kind="cf").inc(2)
    r.gauge("queue_depth", "Current queue depth.").set(5)
    h = r.histogram("latency_s", "Request latency.", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    res = r.reservoir("eps_granted", "Granted eps.", capacity=8)
    for v in (0.1, 0.2, 0.3, 0.4):
        res.observe(v)
    return r


def test_snapshot_schema_is_valid():
    snap = _golden_registry().snapshot()
    assert validate_snapshot(snap) == []
    json.dumps(snap)  # must be JSON-able as-is


def test_snapshot_matches_golden():
    got = json.dumps(
        _golden_registry().snapshot(), indent=2, sort_keys=True
    ) + "\n"
    want = (GOLDEN / "metrics_snapshot.json").read_text()
    assert got == want, (
        "metrics snapshot drifted from tests/golden/metrics_snapshot.json — "
        "if the change is intentional, bump SCHEMA_VERSION and regenerate"
    )


def test_prometheus_matches_golden():
    got = _golden_registry().to_prometheus()
    want = (GOLDEN / "metrics.prom").read_text()
    assert got == want, (
        "Prometheus exposition drifted from tests/golden/metrics.prom — "
        "if the change is intentional, regenerate the golden file"
    )


def test_validate_snapshot_flags_drift():
    snap = _golden_registry().snapshot()
    snap["counters"][0].pop("help")
    assert validate_snapshot(snap)
    assert validate_snapshot({"schema": 1}) != []


# ---------------------------------------------------------------------------
# ServeMetrics on the registry (satellite: bounded memory, compat summary)
# ---------------------------------------------------------------------------

def _response(i: int, *, kind="knn", reexecuted=False, refined=1,
              escalated=False, proxy=None) -> Response:
    return Response(
        rid=i, kind=kind, stage1=0, refined=refined, eps_granted=0.1,
        compression_ratio=20.0, deadline_s=1.0, queue_wait_s=0.0,
        stage1_latency_s=0.001 * (i % 100 + 1),
        total_latency_s=0.002 * (i % 100 + 1),
        deadline_met=True, escalated=escalated, reexecuted=reexecuted,
        accuracy_proxy=proxy,
    )


def test_serve_metrics_memory_flat_over_10k_records():
    m = ServeMetrics(capacity=128)
    for i in range(10_000):
        m.record(_response(i, proxy=0.1))
    # Every reservoir series is capped; exact counts survive.
    for fam_name in ("serve_stage1_latency_ms", "serve_total_latency_ms",
                     "serve_eps_granted", "serve_accuracy_proxy"):
        for _, series in m.registry.get(fam_name).series():
            assert len(series.samples) <= 128
            assert series.count == 10_000
    s = m.summary()
    assert s["n_requests"] == 10_000
    assert s["eps_granted"] == {"mean": pytest.approx(0.1),
                                "min": 0.1, "max": 0.1}
    assert s["accuracy_proxy"]["n"] == 10_000


def test_serve_metrics_summary_compat_keys_and_rates():
    m = ServeMetrics()
    m.record(_response(0, refined=None, escalated=True))
    m.record(_response(0, reexecuted=True))
    m.record_batch(100, occupancy=1, cache_source="built")
    m.record_batch(50, occupancy=1, cache_source="hit")
    s = m.summary(cache_stats={"hits": 1, "misses": 1, "coarsened_hits": 0})
    assert s["n_requests"] == 1 and s["n_reexecutions"] == 1
    assert s["n_batches"] == 2
    assert s["shuffle_bytes_total"] == 150
    assert s["mean_batch_occupancy"] == 1.0
    assert s["escalated_rate"] == 1.0     # over firsts only
    assert s["refined_rate"] == 0.5       # over all responses
    assert s["deadline_met_rate"] == 1.0
    assert s["cache"]["coarsened_hit_rate"] == 0.0
    # Cache-source attribution landed in the registry.
    src = m.registry.get("serve_cache_source_total")
    assert {lbl["source"]: c.value for lbl, c in src.series()} == {
        "built": 1.0, "hit": 1.0,
    }


def test_serve_metrics_empty_summary_is_nan():
    s = ServeMetrics().summary()
    assert math.isnan(s["stage1_latency_ms"]["p50"])
    assert math.isnan(s["eps_granted"]["mean"])
    assert math.isnan(s["deadline_met_rate"])
    assert "accuracy_proxy" not in s


def test_serve_metrics_snapshot_and_reset():
    m = ServeMetrics()
    m.record(_response(1))
    assert validate_snapshot(m.snapshot()) == []
    m.reset()
    assert m.summary()["n_requests"] == 0
    assert m.n_batches == 0


def test_slo_class_buckets():
    assert slo_class(0.005) == "lt10ms"
    assert slo_class(0.05) == "lt100ms"
    assert slo_class(0.5) == "lt1s"
    assert slo_class(10.0) == "ge1s"


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


def test_span_nesting_and_walk():
    tr = Tracer(clock=_fake_clock())
    with tr.span("root", kind="knn") as root:
        with tr.span("child_a"):
            with tr.span("leaf"):
                pass
        with tr.span("child_b") as b:
            b.set(x=1)
    (got,) = tr.traces()
    assert got is root
    assert [s.name for s in got.walk()] == [
        "root", "child_a", "leaf", "child_b",
    ]
    assert got.find("leaf")[0].parent_id == got.find("child_a")[0].span_id
    assert got.attrs == {"kind": "knn"}
    assert got.find("child_b")[0].attrs == {"x": 1}
    assert all(s.duration_s >= 0 for s in got.walk())
    assert got.duration_s > got.find("child_a")[0].duration_s


def test_add_span_and_event_record_explicit_times():
    tr = Tracer(clock=_fake_clock())
    with tr.span("root"):
        tr.add_span("queued", 0.25, 0.75, rid=7)
        tr.event("marker", shard=3)
    (root,) = tr.traces()
    queued = root.find("queued")[0]
    assert (queued.t_start, queued.t_end) == (0.25, 0.75)
    assert queued.attrs == {"rid": 7}
    marker = root.find("marker")[0]
    assert marker.duration_s == 0.0 and marker.attrs == {"shard": 3}


def test_tracer_jsonl_schema_and_render():
    tr = Tracer(clock=_fake_clock())
    with tr.span("root"):
        with tr.span("inner", bytes=128):
            pass
    text = tr.to_jsonl()
    assert validate_trace_jsonl(text) == []
    lines = [json.loads(l) for l in text.splitlines()]
    assert [l["name"] for l in lines] == ["root", "inner"]
    assert lines[1]["parent"] == lines[0]["span"]
    dump = tr.render()
    assert "root" in dump and "inner" in dump and "bytes=128" in dump


def test_tracer_bounds_finished_traces():
    tr = Tracer(clock=_fake_clock(), max_traces=3)
    for i in range(5):
        with tr.span(f"t{i}"):
            pass
    assert [t.name for t in tr.traces()] == ["t2", "t3", "t4"]
    assert tr.dropped_traces == 2


def test_use_tracer_propagation():
    assert current_tracer() is NULL_TRACER
    tr = Tracer(clock=_fake_clock())
    with use_tracer(tr):
        assert current_tracer() is tr
        with current_tracer().span("via_context"):
            pass
    assert current_tracer() is NULL_TRACER
    assert tr.traces()[0].name == "via_context"


def test_null_tracer_is_a_noop():
    sp = NULL_TRACER.span("x", a=1)
    with sp as s:
        s.set(b=2)
    assert NULL_TRACER.traces() == []
    assert not NULL_TRACER.enabled


# ---------------------------------------------------------------------------
# engine tracing
# ---------------------------------------------------------------------------

def test_engine_records_map_and_reduce_spans():
    eng = engine_lib.MapReduce(mesh=None)
    x = jnp.ones((16, 4))
    tr = Tracer()
    with use_tracer(tr):
        eng.run(
            lambda a: a * 2,
            engine_lib.CombineSpec(mode="psum", reduce_fn=lambda o: o + 1),
            x,
        )
    (root,) = tr.traces()
    assert root.name == "mapreduce"
    assert root.attrs["shards"] == 1
    assert root.attrs["shuffle_bytes"] == 16 * 4 * 4
    names = [s.name for s in root.walk()]
    assert "map.shard" in names and "reduce" in names
    assert root.find("map.shard")[0].attrs["shuffle_bytes"] == 16 * 4 * 4


def test_engine_untraced_path_records_nothing():
    eng = engine_lib.MapReduce(mesh=None)
    x = jnp.ones((4, 4))
    out = eng.run(lambda a: a * 2, engine_lib.CombineSpec(mode="psum"), x)
    assert current_tracer() is NULL_TRACER
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2)


class _Counting:
    """Stand-in for a JAX entry point that counts its calls."""

    def __init__(self, real):
        self.real, self.calls = real, []

    def __call__(self, *args, **kwargs):
        self.calls.append(args[0] if args else None)
        return self.real(*args, **kwargs)


@pytest.mark.parametrize("mode", ["all_gather", "psum", "identity"])
def test_engine_never_blocks_under_live_tracer(mode, monkeypatch):
    """Spans time host work; the device trace says when the work ran, so
    a live tracer adds no sync to the engine."""
    block = _Counting(jax.block_until_ready)
    monkeypatch.setattr(jax, "block_until_ready", block)
    eng = engine_lib.MapReduce(mesh=None)
    x = jnp.ones((8, 4))
    tr = Tracer()
    with use_tracer(tr):
        out = eng.run(
            lambda a: a * 2,
            engine_lib.CombineSpec(mode=mode, reduce_fn=lambda o: o + 1),
            x,
        )
    assert block.calls == []
    (root,) = tr.traces()
    want = ["map.shard"] + ([] if mode == "identity" else
                            ["map.meter", "reduce"])
    assert [c.name for c in root.children] == want
    assert np.asarray(out).shape[-1] == 4


# ---------------------------------------------------------------------------
# kernel probe
# ---------------------------------------------------------------------------

def test_kernel_probe_records_host_level_calls():
    reg = MetricsRegistry()
    probe = install_kernel_probe(reg)
    try:
        a = jnp.ones((4, 8))
        b = jnp.ones((16, 8))
        kernel_ops.knn_distance(a, b)
        kernel_ops.knn_distance(a, b)
    finally:
        uninstall_kernel_probe()
    s = probe.summary()
    assert "knn_distance[ref]" in s
    row = s["knn_distance[ref]"]
    assert row["count"] == 2
    assert row["p50_s"] >= 0 and row["bytes"] > 0


def test_kernel_probe_skips_calls_inside_jit():
    reg = MetricsRegistry()
    probe = install_kernel_probe(reg)
    try:
        @jax.jit
        def outer(a, b):
            return kernel_ops.knn_distance(a, b) * 2

        jax.block_until_ready(outer(jnp.ones((4, 8)), jnp.ones((16, 8))))
        assert probe.summary() == {}  # in-trace: clock would be a lie
    finally:
        uninstall_kernel_probe()


def test_kernel_probe_uninstall_restores_lean_path():
    uninstall_kernel_probe()
    assert kernel_ops.get_probe() is None
    d = kernel_ops.knn_distance(jnp.ones((2, 4)), jnp.ones((8, 4)))
    assert d.shape == (2, 8)


def test_kernel_probe_preserves_op_results():
    reg = MetricsRegistry()
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    b = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bare = kernel_ops.knn_distance(a, b)
    install_kernel_probe(reg)
    try:
        probed = kernel_ops.knn_distance(a, b)
    finally:
        uninstall_kernel_probe()
    np.testing.assert_array_equal(np.asarray(bare), np.asarray(probed))


# ---------------------------------------------------------------------------
# runtime shard events (satellite: dormant heartbeats wired to obs)
# ---------------------------------------------------------------------------

def test_supervisor_emits_shard_lifecycle_events(tmp_path):
    from repro.checkpoint import Checkpointer
    from repro.runtime.fault_tolerance import FailureInjector, Supervisor

    fam = default_registry().counter(
        "runtime_shard_events_total", labels=("event", "shard")
    )
    before = {
        e: fam.labels(event=e, shard=0).value
        for e in ("started", "straggling", "finished")
    }
    tr = Tracer()
    with use_tracer(tr):
        sup = Supervisor(
            Checkpointer(str(tmp_path)), save_every=100,
            injector=FailureInjector({2: "straggler"}),
        )
        state, info = sup.run(
            jnp.zeros(()), lambda s, step: s + 1, num_steps=5
        )
    assert float(state) == 5.0
    assert len(info["stragglers"]) == 1
    for e, delta in (("started", 1), ("straggling", 1), ("finished", 1)):
        assert fam.labels(event=e, shard=0).value == before[e] + delta, e
    names = [sp.name for root in tr.traces() for sp in root.walk()]
    assert "shard.started" in names
    assert "shard.straggling" in names
    assert "shard.finished" in names
    straggle = next(
        sp for root in tr.traces() for sp in root.walk()
        if sp.name == "shard.straggling"
    )
    assert straggle.attrs["eps"] > 0


# ---------------------------------------------------------------------------
# serving-path span tree end to end (tentpole acceptance)
# ---------------------------------------------------------------------------

N_KNN, D_KNN, N_CLASSES = 256, 8, 5


def _make_knn_servable():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N_KNN, D_KNN))
    y = jax.random.randint(jax.random.fold_in(key, 1), (N_KNN,), 0, N_CLASSES)
    return KNNServable(x, y, n_classes=N_CLASSES, k=3,
                       lsh_key=jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def knn_servable():
    return _make_knn_servable()


@pytest.fixture
def fresh_knn_servable():
    """A servable of its own: its aggregate store starts empty."""
    return _make_knn_servable()


def _traced_server(knn_servable):
    policy = BudgetPolicy(
        compression_ratio=20.0, eps_max=0.32, degrade_floor=0.004
    )
    ctl = DeadlineController(policy, ema=0.0)
    ctl.set_model(
        "knn", CostModel(c_fixed=0.0, c_stage1=0.0, c_stage2=1.0 / N_KNN)
    )
    return Server(
        [knn_servable],
        controller=ctl,
        batcher=ContinuousBatcher(max_batch=4, pad_sizes=(4,)),
        tracer=Tracer(),
    )


def test_server_submit_drain_produces_full_span_tree(knn_servable):
    server = _traced_server(knn_servable)
    rid = server.submit("knn", (knn_servable.train_x[0],), deadline_s=10.0)
    server.submit("knn", (knn_servable.train_x[1],), deadline_s=10.0)
    responses = server.drain()
    assert {r.rid for r in responses} >= {rid}

    (root,) = server.tracer.traces()
    assert root.name == "serve.batch"
    assert root.attrs["kind"] == "knn" and root.attrs["n"] == 2
    assert root.attrs["shuffle_bytes"] > 0

    # Every stage of the anytime path shows up, correctly nested.
    assert len(root.find("batcher.wait")) == 2
    grant = root.find("deadline.grant")[0]
    assert grant.attrs["eps"] == 0.32 and grant.attrs["refine_budget"] > 0
    lookup = root.find("cache.lookup")[0]
    assert lookup.attrs == {"hit": False, "source": "built"}
    assert root.find("store.get")[0].parent_id == lookup.span_id
    stage1 = root.find("stage1")[0]
    mr = root.find("mapreduce")
    assert len(mr) == 2                      # one per stage
    assert mr[0].parent_id == stage1.span_id
    shard = root.find("map.shard")[0]
    assert shard.attrs["shuffle_bytes"] > 0
    assert shard.duration_s >= 0
    refine = root.find("stage2.refine")[0]
    assert refine.attrs["refine_budget"] == grant.attrs["refine_budget"]
    assert root.find("reduce")

    # Exports validate against their pinned schemas.
    assert validate_trace_jsonl(server.tracer.to_jsonl()) == []
    assert validate_snapshot(server.metrics.snapshot()) == []


def test_server_second_batch_traces_cache_hit(knn_servable):
    server = _traced_server(knn_servable)
    for _ in range(2):
        server.submit("knn", (knn_servable.train_x[0],), deadline_s=10.0)
        server.drain()
    first, second = server.tracer.traces()
    assert first.find("cache.lookup")[0].attrs["hit"] is False
    assert second.find("cache.lookup")[0].attrs["hit"] is True
    # A hit never touches the store: no store.get child.
    assert second.find("store.get") == []


def test_server_records_accuracy_proxy_end_to_end(knn_servable):
    server = _traced_server(knn_servable)
    server.submit("knn", (knn_servable.train_x[0],), deadline_s=10.0)
    (resp,) = server.drain()
    assert resp.refined is not None
    assert resp.accuracy_proxy is not None
    assert 0.0 <= resp.accuracy_proxy <= 1.0
    s = server.summary()
    assert s["accuracy_proxy"]["n"] == 1
    assert s["accuracy_proxy"]["mean"] == pytest.approx(resp.accuracy_proxy)


def test_untraced_server_stays_lean(knn_servable):
    server = Server(
        [knn_servable],
        controller=DeadlineController(
            BudgetPolicy(compression_ratio=20.0, eps_max=0.32), ema=0.0
        ),
        batcher=ContinuousBatcher(max_batch=4, pad_sizes=(4,)),
    )
    assert server.tracer is NULL_TRACER
    server.submit("knn", (knn_servable.train_x[0],), deadline_s=10.0)
    (resp,) = [r for r in server.drain() if not r.reexecuted]
    assert resp.stage1 is not None
    assert NULL_TRACER.traces() == []


def test_serve_batch_children_are_pinned(fresh_knn_servable):
    """The per-batch tree's root and its direct children, by name: readers
    of the tree (per-layer latency metrics) find each stage there."""
    sv = fresh_knn_servable
    server = _traced_server(sv)
    server.submit("knn", (sv.train_x[0],), deadline_s=10.0)
    server.submit("knn", (sv.train_x[1],), deadline_s=10.0)
    server.step()
    (root,) = server.tracer.traces()
    assert root.name == "serve.batch" and root.parent_id is None
    assert [c.name for c in root.children] == [
        "batcher.wait", "batcher.wait", "deadline.grant", "cache.lookup",
        "stage1", "stage2.refine", "serve.respond",
    ]
    for stage in ("stage1", "stage2.refine"):
        (sp,) = root.find(stage)
        assert [c.name for c in sp.children] == ["mapreduce"]


def test_only_live_spans_open_profiler_annotations(fresh_knn_servable,
                                                   monkeypatch):
    """NULL_TRACER never enters ``jax.profiler.TraceAnnotation``; a live
    tracer opens ``host.<name>`` for each ``span`` and none for
    ``add_span``/``event`` (already over when recorded)."""
    sv = fresh_knn_servable
    ann = _Counting(jax.profiler.TraceAnnotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    untraced = Server(
        [sv],
        controller=DeadlineController(
            BudgetPolicy(compression_ratio=20.0, eps_max=0.32), ema=0.0
        ),
        batcher=ContinuousBatcher(max_batch=4, pad_sizes=(4,)),
    )
    untraced.submit("knn", (sv.train_x[0],), deadline_s=10.0)
    untraced.drain()
    with NULL_TRACER.span("x"):
        NULL_TRACER.event("y")
    assert ann.calls == []

    tr = Tracer()
    with tr.span("outer"):
        tr.add_span("waited", 0.0, 1.0)
        tr.event("marker")
        with tr.span("inner"):
            pass
    assert ann.calls == ["host.outer", "host.inner"]


def test_spans_nest_on_the_profiler_timeline(fresh_knn_servable, tmp_path):
    """A profile around one traced ``Server.step`` holds the program's spans
    as ``host.*`` events, nested by time as the span tree nests."""
    from bench import devtrace

    sv = fresh_knn_servable
    server = _traced_server(sv)
    server.submit("knn", (sv.train_x[0],), deadline_s=10.0)
    server.drain()                      # compile outside the profile
    server.submit("knn", (sv.train_x[1],), deadline_s=10.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.step()
    finally:
        jax.profiler.stop_trace()
    events = devtrace.read_profile(str(tmp_path))
    by_name: dict[str, list] = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)

    def inside(inner, outer):
        return (outer.start_ns <= inner.start_ns
                and inner.end_ns <= outer.end_ns)

    (batch,) = by_name["host.serve.batch"]
    (stage1,) = by_name["host.stage1"]
    (respond,) = by_name["host.serve.respond"]
    assert inside(stage1, batch) and inside(respond, batch)
    assert respond.start_ns >= stage1.end_ns
    (mr,) = [m for m in by_name["host.mapreduce"] if inside(m, stage1)]
    for name in ("host.map.shard", "host.map.meter", "host.reduce"):
        assert any(inside(e, mr) for e in by_name[name]), name
    (refine,) = by_name["host.stage2.refine"]
    assert inside(refine, batch) and refine.start_ns >= stage1.end_ns
    assert respond.start_ns >= refine.end_ns


def _small_knn_map_args():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N_KNN, D_KNN))
    y = jax.random.randint(jax.random.fold_in(key, 1), (N_KNN,), 0,
                           N_CLASSES)
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=32)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(7), D_KNN, cfg)
    agg = knn_lib.build_knn_aggregates(x, y, params, N_CLASSES)
    return knn_lib.accurateml_map, (x, y, agg, x[:4]), {"k": 3}


def _small_cf_map_args():
    key = jax.random.PRNGKey(2)
    m = (jax.random.uniform(key, (96, 24)) < 0.3).astype(jnp.float32)
    r = (jax.random.uniform(jax.random.fold_in(key, 1), (96, 24)) * 4 + 1) * m
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=16)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(8), 24, cfg)
    agg = cf_lib.build_cf_aggregates(r, m, params)
    return cf_lib.accurateml_map, (r, m, agg, r[:4], m[:4]), {}


STAGE2_SCOPES = ("stage2.centroids", "stage2.select", "stage2.rows",
                 "stage2.merge")


@pytest.mark.parametrize("app", ["knn", "cf"])
@pytest.mark.parametrize("budget", [0, 24])
def test_map_device_phases_carry_named_scopes(app, budget):
    """The lowered map names its device phases: ``stage1`` at budget 0, the
    four ``stage2.*`` phases (and no ``stage1``) when it refines."""
    fn, args, kw = {"knn": _small_knn_map_args,
                    "cf": _small_cf_map_args}[app]()
    text = fn.lower(
        *args, refine_budget=budget, with_bound=True, **kw
    ).as_text(debug_info=True)
    scopes = set(re.findall(r'"jit\(accurateml_map\)/([^/"]+)/', text))
    want = {"stage1"} if budget == 0 else set(STAGE2_SCOPES)
    assert scopes == want


def test_knn_accuracy_proxy_is_zero_for_identical_outputs(knn_servable):
    q = knn_servable.train_x[:2]
    out = knn_servable.run(
        knn_servable.build(20.0), (q,), refine_budget=0
    )
    proxies = knn_servable.accuracy_proxy(out, out, 2)
    assert proxies == [0.0, 0.0]
