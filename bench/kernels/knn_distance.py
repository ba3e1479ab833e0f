"""Squared-L2 distance matrix (kNN stage 1 inside the refined map).

q queries against n points of d features: the cross products and norms;
one read of the points and queries and the [q, n] distances written.
"""
MATCH = [r"%knn_distance_pallas[.\d]* = "]


def work(*, q: int, n: int, d: int) -> tuple[float, float]:
    flops = 2.0 * q * n * d + 2.0 * n * d + 3.0 * q * n
    nbytes = 4.0 * (n * d + q * d + q * n)
    return flops, nbytes
