"""Fused squared-L2 distance + streaming top-k (kNN stage 1).

Algorithmic work of q queries against n points of d features, keeping k:
the cross products and norms, and one read of the points with their
labels and validity flags plus the queries and the k-best written back.
"""
MATCH = [r"%distance_topk_pallas[.\d]* = "]


def work(*, q: int, n: int, d: int, k: int) -> tuple[float, float]:
    flops = 2.0 * q * n * d + 2.0 * n * d + 3.0 * q * n
    nbytes = 4.0 * (n * d + 2 * n + q * d + 2 * q * k)
    return flops, nbytes
