"""Exact neighbour terms of each active user's own b refined users over
i items (CF stage 2): per pair four contractions for the shrunk Pearson
weight and two accumulations; each pair reads the candidate's centred
ratings and mask row once, and the sums are written once per user."""
MATCH = [
    r"%cf_refine_pallas[.\d]* = ",
    # inside the chunk loop: ([Q, C, 1, 1] weights, [Q, 1, I] sums x2)
    r"= \(f32\[\d+,\d+,1,1\](\{[^}]*\})?, f32\[\d+,1,\d+\].*custom-call\(s32\[",
]


def work(*, q: int, b: int, i: int) -> tuple[float, float]:
    pairs = q * b
    flops = 12.0 * pairs * i
    nbytes = 4.0 * (2 * pairs * i + 3 * pairs + 4 * q * i)
    return flops, nbytes
