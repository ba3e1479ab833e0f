"""Host time per batch, ms: `serve.batch` minus its `stage1` and
`stage2.refine` children (grant, cache lookup, padding, unpacking)."""
from bench.layers import batch_spans


def read(ctx):
    vals = [s["batch_s"] - s["stage1_s"] - s["stage2_s"] for s in batch_spans(ctx)]
    return 1e3 * sum(vals) / len(vals) if vals else None
