"""Exact distances of each query to its own b refined rows (kNN stage 2).

Per query, Algorithm 1 refines its own rows: q * b rows of d features read
once each with their indices and validity flags, one distance written per
row.  Copies the implementation makes of the table are not work.
"""
MATCH = [
    r"%refine_distances_pallas[.\d]* = ",
    # inside the chunk loop: one [Q, C, 1, 1] distance block per chunk
    r"= f32\[\d+,\d+,1,1\](\{[^}]*\})? custom-call\(s32\[",
]


def work(*, q: int, b: int, d: int) -> tuple[float, float]:
    rows = q * b
    flops = 3.0 * rows * d
    nbytes = 4.0 * (rows * d + 3 * rows + q * d)
    return flops, nbytes
