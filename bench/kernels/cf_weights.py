"""Masked Pearson weights of q active users against u rows of i items
(CF stage 1 against the aggregated users): three contractions
(numerator, the two masked norms), one read of both operands' centred
ratings and masks, the [q, u] weights written."""
MATCH = [r"%cf_weights_pallas[.\d]* = "]


def work(*, q: int, u: int, i: int) -> tuple[float, float]:
    flops = 6.0 * q * u * i
    nbytes = 4.0 * (2 * u * i + 2 * q * i + q * u)
    return flops, nbytes
