"""95th percentile of the gaps between consecutive tokens of one request,
over every gap that ends in the window (host clock, client side)."""

from chipbench import reduce


def read(run):
    v = reduce.pct(reduce.itls(run), 95)
    return None if v is None else v * 1e3
