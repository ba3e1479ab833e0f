"""Arithmetic the readers of the program's own spans share.

While the program's tracer is live, each span it opens is also a profiler
annotation ``host.<span>`` (``host.map.meter``, ``host.reduce``, ...) on the
device trace's clock, so ``devtrace.reduce`` labels an idle gap of the
device by the innermost program span covering it.
"""
from __future__ import annotations


def idle_ms_per_batch(ctx, label: str):
    """Idle device time (ms) labelled by host span ``label``, per batch;
    None when the label is not among the breakdown's ``idle_gaps`` (which
    keeps the ten longest labels, so a label below the tenth reads None)."""
    if not ctx.device.ops or not ctx.batches:
        return None
    idle_s = dict(ctx.device.idle_gaps).get(label)
    if idle_s is None:
        return None
    return 1e3 * idle_s / len(ctx.batches)
