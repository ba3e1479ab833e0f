"""The readers of the program's own spans, on synthetic device traces:
idle device time per batch under the engine's `map.meter` and `reduce`."""
import types

import pytest

from bench import devtrace, registry
from bench.serving import Batch

IDLE_READERS = {"meter_idle_ms_per_batch": "host.map.meter",
                "reduce_idle_ms_per_batch": "host.reduce"}


def _ev(name, start, dur, plane="/device:TPU:0", line=devtrace.OPS_LINE):
    return devtrace.Event(plane, line, name, float(start), float(dur))


def _host(name, start, dur):
    return _ev(name, start, dur, plane="/host:CPU", line="python3")


def _ctx(device, n_batches):
    batches = [Batch(rids=[0], n=1, padded=4, eps=0.0, refine_budget=0,
                     reexecution=False, outputs=[])
               for _ in range(n_batches)]
    return types.SimpleNamespace(batches=batches, device=device)


def _reduced(ops, idle_gaps):
    return devtrace.Reduced(window_s=1.0, busy_s=0.5, ops=list(ops),
                            top_ops=[], idle_gaps=list(idle_gaps))


def _read(name, ctx):
    return registry.metric(name).read(ctx)


@pytest.mark.parametrize("name,label", sorted(IDLE_READERS.items()))
def test_idle_readers_read_their_label_per_batch(name, label):
    ops = [_ev("%fusion.1 = f32[4,5] fusion(f32[4,5] %d)", 0, 1e6)]
    gaps = [("host.idle", 0.5), (label, 0.004), ("host.unpack", 0.002)]
    assert _read(name, _ctx(_reduced(ops, gaps), 4)) == pytest.approx(1.0)
    # a label missing from the ten longest reads nothing
    assert _read(name, _ctx(_reduced(ops, gaps[:1]), 4)) is None


@pytest.mark.parametrize("name", sorted(IDLE_READERS))
def test_idle_readers_find_nothing_without_device_ops(name):
    gaps = [(label, 0.1) for label in IDLE_READERS.values()]
    assert _read(name, _ctx(_reduced([], gaps), 2)) is None


def test_program_spans_inside_the_dispatch_take_its_idle_time():
    """A map call as the program traces it inside the harness's dispatch
    annotation: each gap goes to the innermost program span."""
    events = [
        _host(devtrace.WINDOW, 0, 100),
        _host("host.dispatch.budget0", 0, 90),
        _host("host.mapreduce", 1, 88),
        _host("host.map.shard", 1, 4),
        _host("host.map.meter", 5, 25),
        _host("host.reduce", 30, 59),
        _ev("%distance_topk_pallas.1 = f32[4,5] custom-call(x)", 4, 6),
        _ev("%neg.1 = f32[4,5] negate(x)", 30, 2),
        _ev("%reduce.2 = f32[4] reduce(x)", 60, 2),
        _ev("%fusion.3 = f32[4] fusion(x)", 92, 8),
    ]
    ctx = _ctx(devtrace.reduce(events), 2)
    # gaps by their middles: [0, 4) map.shard, [10, 30) map.meter,
    # [32, 60) and [62, 92) reduce; none left to the dispatch
    gaps = dict(ctx.device.idle_gaps)
    assert gaps == pytest.approx({"host.map.shard": 4e-9,
                                  "host.map.meter": 20e-9,
                                  "host.reduce": 58e-9})
    assert _read("meter_idle_ms_per_batch", ctx) == pytest.approx(1e-5)
    assert _read("reduce_idle_ms_per_batch", ctx) == pytest.approx(2.9e-5)
