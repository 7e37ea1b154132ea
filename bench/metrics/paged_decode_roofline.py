"""Roofline share of the ragged paged decode kernel
(``kernels/paged_attention.py``), in %: the work of one call at the true
context lengths and the pool's itemsize, over its mean device time."""
from bench.metrics_common import kernel_roofline


def read(record):
    return kernel_roofline(record, "decode")
