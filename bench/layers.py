"""Arithmetic the per-layer readers share: span means, roofline shares."""
from __future__ import annotations


def batch_spans(ctx) -> list[dict]:
    return [b.spans for b in ctx.batches if b.spans is not None]


def span_mean_ms(ctx, key: str, *, only_nonzero: bool = False):
    """Mean over batches of one span duration (ms); None with no spans."""
    vals = []
    for s in batch_spans(ctx):
        v = s[key]
        if isinstance(v, list):
            vals.extend(v)
        elif not only_nonzero or v > 0:
            vals.append(v)
    return 1e3 * sum(vals) / len(vals) if vals else None


def min_time_s(ctx, flops: float, nbytes: float) -> float:
    """Least time the chip needs for the work: the larger of the two
    roofline bounds (peak FLOP/s, peak HBM bytes/s)."""
    peaks = ctx.peaks
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def kernel_roofline(ctx, name: str):
    """Share (%) of the kernel's device time that its algorithmic work
    needs at the roofline; None when the window ran no such call."""
    kernel = ctx.kernel(name)
    need = 0.0
    for b in ctx.batches:
        stages = ["stage1"] + (["stage2"] if b.refine_budget > 0 else [])
        for stage in stages:
            for kname, shape in ctx.app.kernel_calls(
                    ctx.cfg, b.n, b.refine_budget, stage):
                if kname == name:
                    need += min_time_s(ctx, *kernel.work(**shape))
    spent = ctx.device.kernel_s(kernel.MATCH)
    if need <= 0.0 or spent <= 0.0:
        return None
    return 100.0 * need / spent


def map_share(ctx, parts: tuple[str, ...]):
    """Share (%) of the traced window the chip needs, at the roofline, for
    the algorithmic work of every batch answered in it (``parts`` of
    ``app.map_work``: stage 1 always, stage 2 where the batch refined)."""
    need = 0.0
    for b in ctx.batches:
        work = ctx.app.map_work(ctx.cfg, b.n, b.refine_budget)
        for part in parts:
            if part == "stage2" and b.refine_budget <= 0:
                continue
            need += min_time_s(ctx, *work[part])
    if need <= 0.0 or ctx.device.window_s <= 0.0:
        return None
    return 100.0 * need / ctx.device.window_s
